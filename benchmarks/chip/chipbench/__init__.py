"""The chip benchmark of the served coded matmul.

``benchmarks/chip/run.py`` is the entry point.  Everything that belongs to
one configuration, one traffic mix, one cell's limits or one metric sits in
a file of its own under ``benchmarks/chip`` and is found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the deployment's sizes and guarantees, and
  ``configs/<config>.py`` beside it: ``build_code`` (the program's code
  object for that deployment) and ``reference`` (a plain numpy statement of
  the answer, importing nothing of the program).  Its ``job`` gives
  ``rows``, ``inner`` and ``cols``, and may say ``"fixed": "B"``: one
  read-only ``B``, drawn once a run, is then every request's ``B``, the
  same object for the whole run, on which a program may key a cache of
  its encoded weight (:mod:`chipbench.operands`);
* ``traffic/<traffic>.json`` — the load: open or closed loop, arrival
  process and rate, accuracy target;
* ``limits/<workload>.json`` — the numbers that decide ``correct`` and
  their limits;
* ``metrics/<metric>.py`` — one reader per metric, ``read(run)``.
"""

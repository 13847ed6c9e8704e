"""The chip benchmark of the served coded matmul.

``benchmarks/chip/run.py`` is the entry point.  Everything that belongs to
one configuration, one traffic mix, one cell's limits or one metric sits in
a file of its own under ``benchmarks/chip`` and is found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` — the deployment's sizes and guarantees, and
  ``configs/<config>.py`` beside it: ``build_code`` (the program's code
  object for that deployment) and ``reference`` (a plain numpy statement of
  the answer, importing nothing of the program);
* ``traffic/<traffic>.json`` — the load: open or closed loop, arrival
  process and rate, accuracy target;
* ``limits/<workload>.json`` — the numbers that decide ``correct`` and
  their limits;
* ``metrics/<metric>.py`` — one reader per metric, ``read(run)``.
"""

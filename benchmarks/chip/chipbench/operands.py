"""Request operands, drawn on the device from the run's seed.

By default every request has a pair ``(A, B)`` of its own.  A
configuration whose ``job`` says ``"fixed": "B"`` serves a fixed weight
instead: ``B`` is drawn once a run, before the warm-up, and every request
of the run (the warm-up, the window and the comparison after it) gets that
same ``ndarray`` object as its ``B``, while each request still draws an
``A`` of its own.

The harness's contract with the program: the fixed operand is one
read-only object (``flags.writeable`` is false) that lives for the whole
run, so a program may key a cache of its encoded weight on that object.
The harness tells the program nothing through any other channel.

Host memory: a pool of ``n`` pairs holds ``n·(rows·inner + inner·cols)``
float64 entries, and ``n·rows·inner + inner·cols`` with ``"fixed": "B"``.
A fixed-weight cell sizes ``n`` through its own traffic file's
``pool_per_second``.
"""
from __future__ import annotations

import numpy as np

CHUNK = 16          # requests per jitted draw: one program, small device peak
FIXED = ("B",)      # the operands a job may fix


def fixed_operand(job: dict) -> str | None:
    """The job's ``fixed`` operand, ``None`` without one.

    Any value but ``"B"`` raises ``ValueError``.
    """
    fixed = job.get("fixed")
    if fixed is not None and fixed not in FIXED:
        raise ValueError(f"job 'fixed' is {fixed!r}; the one allowed value "
                         "is 'B' (one B shared by every request)")
    return fixed


def _key(seed: int, stream: int):
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, dtype=jnp.uint32))


def fixed_weight(seed: int, inner: int, cols: int) -> np.ndarray:
    """The run's fixed ``B``: one draw of N(0, 1) entries, float32 on the
    device widened to float64, read-only."""
    import jax
    import jax.numpy as jnp
    B = np.asarray(jax.jit(lambda k: jax.random.normal(
        k, (inner, cols), jnp.float32))(_key(seed, 3)), dtype=np.float64)
    B.flags.writeable = False
    return B


def make_operands(seed: int, n: int, rows: int, inner: int, cols: int,
                  fixed: str | None = None
                  ) -> list[tuple[np.ndarray, np.ndarray]]:
    """``n`` pairs ``(A, B)`` of i.i.d. N(0, 1) float64 entries.

    The draws are float32 on the device (one jitted call per chunk of
    :data:`CHUNK` requests), then widened to the float64 that the §V job
    sends.  The same seed gives the same operands.  Without ``fixed`` the
    ``n`` pairs are distinct.  With ``fixed="B"`` every pair holds the one
    :func:`fixed_weight` object as its ``B``; the ``A`` are those that the
    same seed draws without ``fixed``.
    """
    import jax
    import jax.numpy as jnp

    key = _key(seed, 2)
    B = None if fixed is None else fixed_weight(seed, inner, cols)

    @jax.jit
    def draw(key, i):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.normal(ka, (CHUNK, rows, inner), jnp.float32)
        if B is not None:
            return a, None
        return a, jax.random.normal(kb, (CHUNK, inner, cols), jnp.float32)

    pairs = []
    for c in range(-(-n // CHUNK)):
        a, b = draw(key, c)
        a = np.asarray(a, dtype=np.float64)
        if B is None:
            pairs.extend(zip(a, np.asarray(b, dtype=np.float64)))
        else:
            pairs.extend((A, B) for A in a)
    return pairs[:n]


def derived_pair(pairs, j: int, fixed: str | None = None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Request ``j``'s operands, past the drawn pool by rolling a drawn pair.

    Rolling the rows of ``A`` keeps every request's ``A`` distinct from
    every other's (for ``j`` below ``len(pairs)`` times ``A``'s rows), so
    no cache keyed on ``A`` can hit.  Without ``fixed`` the columns of
    ``B`` roll too, and no cache keyed on either operand can hit; with
    ``fixed="B"`` the shared ``B`` is returned as it is, the same object
    for every request, by design.
    """
    n = len(pairs)
    A, B = pairs[j % n]
    k = j // n
    if k == 0:
        return A, B
    return np.roll(A, k, axis=0), B if fixed == "B" else np.roll(B, k, axis=1)

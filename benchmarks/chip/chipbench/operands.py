"""Request operands, drawn on the device from the run's seed."""
from __future__ import annotations

import numpy as np

CHUNK = 16          # requests per jitted draw: one program, small device peak


def make_operands(seed: int, n: int, rows: int, inner: int,
                  cols: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """``n`` distinct pairs ``(A, B)`` of i.i.d. N(0, 1) float64 entries.

    The draws are float32 on the device (one jitted call per chunk of
    :data:`CHUNK` requests), then widened to the float64 that the §V job
    sends.  The same seed gives the same operands.
    """
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence([seed, 2]).generate_state(2)
    key = jax.random.wrap_key_data(jnp.asarray(words, dtype=jnp.uint32))

    @jax.jit
    def draw(key, i):
        ka, kb = jax.random.split(jax.random.fold_in(key, i))
        return (jax.random.normal(ka, (CHUNK, rows, inner), jnp.float32),
                jax.random.normal(kb, (CHUNK, inner, cols), jnp.float32))

    pairs = []
    for c in range(-(-n // CHUNK)):
        a, b = draw(key, c)
        a = np.asarray(a, dtype=np.float64)
        b = np.asarray(b, dtype=np.float64)
        pairs.extend(zip(a, b))
    return pairs[:n]


def derived_pair(pairs, j: int) -> tuple[np.ndarray, np.ndarray]:
    """Request ``j``'s operands, past the drawn pool by rolling a drawn pair.

    Rolling the rows of ``A`` and the columns of ``B`` keeps every request's
    operands distinct from every other's, so no cache keyed on an operand
    can hit.
    """
    n = len(pairs)
    A, B = pairs[j % n]
    k = j // n
    if k == 0:
        return A, B
    return np.roll(A, k, axis=0), np.roll(B, k, axis=1)

"""One PRNG key from ``--seed``, which may be larger than 32 signed bits hold."""
from __future__ import annotations


def key(seed, *folds):
    import jax

    seed = int(seed)
    k = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)
    for f in folds:
        k = jax.random.fold_in(k, f)
    return k

"""The run's PRNG key from ``--seed``: any whole number in [0, 2**64)."""
from __future__ import annotations

import numpy as np


def seed_key(seed: int):
    """A raw threefry key [hi, lo] of the seed's 64 bits (jax.random.PRNGKey
    keeps only the low 32 bits without x64, so two large seeds would
    collide)."""
    import jax.numpy as jnp
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed {seed} is outside [0, 2**64)")
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))

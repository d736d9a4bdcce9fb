"""LEAD (Liu et al., ICLR 2021, Alg. 1) as plain jax.numpy over a dense W.

Imports nothing of the program.  Buffers are (agents, blocks, block): the
quantizer works per block of ``block`` elements, and the mix is the dense
(agents x agents) W at HIGHEST precision.  ``dtype`` is the precision the
step computes in (float32; bfloat16 for the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dither(key, agents: int, rows: int, block: int):
    """The quantizer's U[0,1) dither: one threefry key per agent, split from
    the step's key for this buffer, and a (rows, block) draw each."""
    keys = jax.random.split(key, agents)
    return jax.vmap(lambda k: jax.random.uniform(
        k, (rows, block), jnp.float32))(keys)


def quantize(v, u, bits: int):
    """The p=inf b-bit stochastic quantizer, decoded: per block the scale is
    max|v|, the level floor(2^(b-1) |v| / scale + u) capped at 2^(b-1)."""
    scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    top = 2.0 ** (bits - 1)
    lvl = jnp.minimum(jnp.floor(top * jnp.abs(v) / safe + u), top)
    code = jnp.sign(v) * lvl
    return jnp.where(scale > 0, scale, 0.0) * (2.0 ** (1 - bits)) * code


def step(x, g, h, hw, d, u, W, *, eta, gamma, alpha, bits: int,
         dtype=jnp.float32):
    """One LEAD iteration (Alg. 1 lines 4-7) on (agents, rows, block)
    buffers; returns the new (x, h, hw, d) in ``dtype``."""
    x, g, h, hw, d, u = (a.astype(dtype) for a in (x, g, h, hw, d, u))
    eta, gamma, alpha = (jnp.asarray(c, dtype) for c in (eta, gamma, alpha))
    y = x - eta * g - eta * d
    q = quantize(y - h, u, bits).astype(dtype)
    wq = jnp.tensordot(jnp.asarray(W, dtype), q, axes=([1], [0]),
                       precision=HIGHEST)
    yh, yhw = h + q, hw + wq
    d2 = d + gamma / (2 * eta) * (yh - yhw)
    return (x - eta * g - eta * d2, (1 - alpha) * h + alpha * yh,
            (1 - alpha) * hw + alpha * yhw, d2)


def ring(n: int):
    """The uniform ring's W: 1/3 to self and to each neighbour (n >= 3),
    [[1]] for one agent, 1/2 everywhere for two."""
    import numpy as np
    if n <= 2:
        return np.full((n, n), 1.0 / n)
    W = np.zeros((n, n))
    for i in range(n):
        for j in (i - 1, i, i + 1):
            W[i, j % n] = 1.0 / 3.0
    return W

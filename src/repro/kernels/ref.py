"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests assert against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax.numpy as jnp


def quantize_encode_ref(x: jnp.ndarray, u: jnp.ndarray, bits: int,
                        block=None):
    """Blockwise inf-norm b-bit stochastic quantization (paper Thm 3, p=inf).

    x, u: (rows, width) f32 with ``width // block`` blocks a row (``block``
    defaults to the width); u ~ U[0,1).  Returns (code int8 (rows, width),
    scale f32 (rows, width // block)).
    """
    rows, width = x.shape
    k = 1 if block is None else width // block
    x = x.reshape(rows, k, width // k)
    u = u.reshape(rows, k, width // k)
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(x) / safe + u)
    lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
    code = (jnp.sign(x) * lvl).astype(jnp.int8)
    return (code.reshape(rows, width),
            jnp.where(scale > 0, scale, 0.0).astype(jnp.float32).reshape(rows, k))


def quantize_decode_ref(code: jnp.ndarray, scale: jnp.ndarray, bits: int):
    """Inverse of quantize_encode_ref: (rows, width) f32 values, each of the
    k = scale.shape[-1] lane segments of a row on its own scale."""
    rows, width = code.shape
    k = scale.shape[-1]
    vals = (scale.reshape(rows, k, 1) * (2.0 ** (1 - bits))
            * code.reshape(rows, k, width // k).astype(jnp.float32))
    return vals.reshape(rows, width)


def lead_update_ref(x, g, d, h, hw, qh, wqh, eta, gamma, alpha):
    """Fused LEAD post-communication state update (Alg. 1 lines 5-7).

    All arrays share one shape; scalars are python/jnp f32.
    Returns (x_new, d_new, h_new, hw_new).
    """
    yh = h + qh
    yhw = hw + wqh
    h_new = (1.0 - alpha) * h + alpha * yh
    hw_new = (1.0 - alpha) * hw + alpha * yhw
    d_new = d + gamma / (2.0 * eta) * (yh - yhw)
    x_new = x - eta * g - eta * d_new
    return x_new, d_new, h_new, hw_new


def lead_diff_encode_ref(x, g, d, h, u, eta, bits):
    """Fused pre-communication kernel: diff = (x - eta g - eta d) - h, then
    blockwise inf-norm b-bit quantization of the diff.

    x, g, d, h, u: (nb, block) f32.  Returns (code int8, scale (nb,1) f32).
    """
    diff = x - eta * g - eta * d - h
    return quantize_encode_ref(diff, u, bits)


def randk_encode_ref(x, u, ratio, scale):
    """Shared-seed random-k keep plane: x * scale where u < ratio, else 0."""
    return jnp.where(u < ratio, x * scale, 0.0)


def mask_apply_ref(x, mask):
    """Top-k value plane: x * mask (mask is an exact-k 0/1 f32 plane)."""
    return x * mask.astype(jnp.float32)

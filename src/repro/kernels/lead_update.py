"""Fused Pallas kernels for the LEAD iteration's elementwise hot path.

Per LEAD step, every parameter element is touched by ~12 separate elementwise
ops (lines 4-7 of Alg. 1).  Unfused, each op is an HBM round trip on arrays
the size of the model — the LEAD update is *memory-bound*.  Two fused kernels
reduce this to two passes:

  * lead_diff_encode — pre-communication: computes
        diff = (X - eta*G - eta*D) - H
    and quantizes it blockwise in one pass (reads X,G,D,H + dither, writes
    int8 codes + scales: ~17 bytes read / ~1 byte written per element instead
    of ~3 intermediate round trips).
  * lead_update — post-communication: given decoded Qh and W*Qh, updates
    X, D, H, H_w in one pass (lines 5-7).

Scalars (eta, gamma, alpha) are passed as (1, 1) f32 arrays so that traced
schedules (Theorem 2 diminishing stepsizes) work under jit.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_backend
from repro.kernels.quantize import DEFAULT_TILE_B, fit_tile


def _lead_update_kernel(eta_ref, gamma_ref, alpha_ref,
                        x_ref, g_ref, d_ref, h_ref, hw_ref, qh_ref, wqh_ref,
                        xo_ref, do_ref, ho_ref, hwo_ref):
    eta = eta_ref[0, 0]
    gamma = gamma_ref[0, 0]
    alpha = alpha_ref[0, 0]
    h = h_ref[...]
    hw = hw_ref[...]
    yh = h + qh_ref[...]
    yhw = hw + wqh_ref[...]
    ho_ref[...] = (1.0 - alpha) * h + alpha * yh
    hwo_ref[...] = (1.0 - alpha) * hw + alpha * yhw
    d_new = d_ref[...] + gamma / (2.0 * eta) * (yh - yhw)
    do_ref[...] = d_new
    xo_ref[...] = x_ref[...] - eta * g_ref[...] - eta * d_new


def lead_update(x, g, d, h, hw, qh, wqh, eta, gamma, alpha, *,
                tile_b: int = DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """All tensors (nb, block) f32, any nb (tile_b caps the rows per grid
    step; see quantize.fit_tile); scalars broadcastable to (1, 1) f32.

    Returns (x_new, d_new, h_new, hw_new)."""
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return tuple(ref.lead_update_ref(x, g, d, h, hw, qh, wqh,
                                         jnp.asarray(eta, jnp.float32),
                                         jnp.asarray(gamma, jnp.float32),
                                         jnp.asarray(alpha, jnp.float32)))
    nb, block = x.shape
    tile_b = fit_tile(nb, tile_b)
    grid = (pl.cdiv(nb, tile_b),)
    scal = lambda v: jnp.asarray(v, jnp.float32).reshape(1, 1)
    tile = pl.BlockSpec((tile_b, block), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0))
    out_sds = jax.ShapeDtypeStruct((nb, block), jnp.float32)
    return pl.pallas_call(
        _lead_update_kernel,
        grid=grid,
        in_specs=[smem, smem, smem] + [tile] * 7,
        out_specs=[tile] * 4,
        out_shape=[out_sds] * 4,
        interpret=(backend == "interpret"),
    )(scal(eta), scal(gamma), scal(alpha), x, g, d, h, hw, qh, wqh)


def _diff_encode_kernel(eta_ref, x_ref, g_ref, d_ref, h_ref, u_ref,
                        code_ref, scale_ref, *, bits: int):
    eta = eta_ref[0, 0]
    diff = x_ref[...] - eta * g_ref[...] - eta * d_ref[...] - h_ref[...]
    scale = jnp.max(jnp.abs(diff), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(diff) / safe + u_ref[...])
    lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
    code_ref[...] = (jnp.sign(diff) * lvl).astype(jnp.int8)
    scale_ref[...] = jnp.where(scale > 0, scale, 0.0).astype(jnp.float32)


def lead_diff_encode(x, g, d, h, u, eta, *, bits: int = 2,
                     tile_b: int = DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """Fused Y-difference + quantization (pre-communication pass).

    x, g, d, h, u: (nb, block) f32.  Returns (code int8, scale (nb,1) f32)."""
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.lead_diff_encode_ref(x, g, d, h, u,
                                        jnp.asarray(eta, jnp.float32), bits)
    nb, block = x.shape
    tile_b = fit_tile(nb, tile_b)
    grid = (pl.cdiv(nb, tile_b),)
    tile = pl.BlockSpec((tile_b, block), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_diff_encode_kernel, bits=bits),
        grid=grid,
        in_specs=[smem] + [tile] * 5,
        out_specs=[
            tile,
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=(backend == "interpret"),
    )(jnp.asarray(eta, jnp.float32).reshape(1, 1), x, g, d, h, u)

"""Pallas TPU kernels for the sparsifying compressors' flat wire paths.

Two fused elementwise passes over the kernels' ``(nb, block)`` layout:

  * randk_encode — shared-seed random-k: mask = (u < ratio) computed from
    the dither plane IN the kernel (no materialized boolean mask round
    trip), values = x * (1/ratio) where kept.  With a shared PRNG seed the
    mask is reproducible at the receiver, so the kept values are the entire
    wire payload (paper App. C.2).
  * mask_apply — threshold+mask for top-k: applies a precomputed keep-mask
    (exact-k, from jax.lax.top_k indices — ties must not inflate the kept
    count past what wire_bits charges) in one read of (x, mask), one write.

Both follow the package's backend dispatch contract (kernels/dispatch.py):
``interpret=None`` auto-resolves to the jnp reference math on CPU and
compiled Pallas on TPU; ``interpret=True`` runs the true interpreter the
kernel tests pin.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_backend
from repro.kernels.quantize import DEFAULT_TILE_B, fit_tile


def _randk_kernel(x_ref, u_ref, out_ref, *, ratio: float, scale: float):
    x = x_ref[...]
    keep = u_ref[...] < ratio
    out_ref[...] = jnp.where(keep, x * scale, 0.0)


def randk_encode(x: jnp.ndarray, u: jnp.ndarray, *, ratio: float,
                 rescale: bool = True, tile_b: int = DEFAULT_TILE_B,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """x, u: (nb, block) f32, any nb (see quantize.fit_tile).  Returns the
    kept-value plane: x * (1/ratio if rescale else 1) where u < ratio,
    else 0."""
    scale = (1.0 / ratio) if rescale else 1.0
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.randk_encode_ref(x, u, ratio, scale)
    nb, block = x.shape
    tile_b = fit_tile(nb, tile_b)
    tile = pl.BlockSpec((tile_b, block), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_randk_kernel, ratio=ratio, scale=scale),
        grid=(pl.cdiv(nb, tile_b),),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=(backend == "interpret"),
    )(x, u)


def _mask_apply_kernel(x_ref, m_ref, out_ref):
    out_ref[...] = x_ref[...] * m_ref[...]


def mask_apply(x: jnp.ndarray, mask: jnp.ndarray, *,
               tile_b: int = DEFAULT_TILE_B,
               interpret: Optional[bool] = None) -> jnp.ndarray:
    """x: (nb, block) f32, mask: same-shape f32 0/1 plane -> x * mask."""
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.mask_apply_ref(x, mask)
    nb, block = x.shape
    tile_b = fit_tile(nb, tile_b)
    tile = pl.BlockSpec((tile_b, block), lambda i: (i, 0))
    return pl.pallas_call(
        _mask_apply_kernel,
        grid=(pl.cdiv(nb, tile_b),),
        in_specs=[tile, tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=(backend == "interpret"),
    )(x, mask.astype(jnp.float32))

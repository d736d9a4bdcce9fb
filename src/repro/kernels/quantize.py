"""Pallas TPU kernels for blockwise inf-norm b-bit stochastic quantization.

TPU adaptation of the paper's quantizer (Theorem 3, p = inf):
  * the quantization *block* (paper: 512 contiguous elements) is laid out as
    rows of a (n_blocks, 512) matrix — 512 = 4 x 128 lanes, so a block is 4
    sublanes and the per-block max reduction is a cheap in-register lane/
    sublane reduce on the VPU;
  * a *tile* of TILE_B blocks is staged into VMEM per grid step, sized so the
    working set (x, u, codes) stays well under VMEM (~16 MB/core);
  * codes are stored in int8 lanes — the natural TPU container; the wire size
    accounting (roofline) uses the true b-bit payload, and bit-packing for
    the ICI transfer is a pure reshape/or-reduce on int8 lanes (see
    ops.pack_codes).

Dither bits `u` arrive as an input (generated with jax.random outside):
on-device pltpu.prng_random_bits is the production path on real TPU but has
no CPU interpret lowering, so the framework keeps the dither explicit —
which also makes the kernels bit-reproducible across backends.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_backend


DEFAULT_BLOCK = 512     # paper's quantization block
DEFAULT_TILE_B = 256    # blocks per grid step: 256*512*4B*3 buffers ~ 1.5 MB VMEM


def fit_tile(n_rows: int, cap: int = DEFAULT_TILE_B) -> int:
    """Rows per grid step for an (n_rows, block) operand — the one tile
    rule of every kernel in this package.

    Mosaic accepts a block whose row count is a multiple of 8 or equals the
    whole array's.  So the tile is the whole row count when it fits under
    ``cap``, and ``cap`` rounded down to a multiple of 8 otherwise.  The
    grid is ``pl.cdiv(n_rows, tile)``: a row count the tile does not divide
    (granite's 49155 x 2048 embedding is 196,620 rows) ends in a partial
    block, which Pallas pads on read and masks on write.  Every kernel here
    is row-independent, so the padded rows never reach a valid one."""
    cap = max(8, cap - cap % 8)
    return n_rows if n_rows <= cap else cap


def _encode_kernel(x_ref, u_ref, code_ref, scale_ref, *, bits: int):
    x = x_ref[...]
    u = u_ref[...]
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    safe = jnp.where(scale > 0, scale, 1.0)
    lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(x) / safe + u)
    lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
    code_ref[...] = (jnp.sign(x) * lvl).astype(jnp.int8)
    scale_ref[...] = jnp.where(scale > 0, scale, 0.0).astype(jnp.float32)


def _decode_kernel(code_ref, scale_ref, out_ref, *, bits: int):
    code = code_ref[...].astype(jnp.float32)
    out_ref[...] = scale_ref[...] * (2.0 ** (1 - bits)) * code


def encode(x: jnp.ndarray, u: jnp.ndarray, *, bits: int = 2,
           tile_b: int = DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """x, u: (nb, block) f32, any nb (``tile_b`` caps the rows per grid
    step; see fit_tile).

    Returns (code int8 (nb, block), scale f32 (nb, 1))."""
    assert 1 <= bits <= 7, "int8 code container supports bits in [1, 7]"
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.quantize_encode_ref(x, u, bits)
    nb, block = x.shape
    tile_b = fit_tile(nb, tile_b)
    grid = (pl.cdiv(nb, tile_b),)
    return pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, block), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, block), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=(backend == "interpret"),
    )(x, u)


def decode(code: jnp.ndarray, scale: jnp.ndarray, *, bits: int = 2,
           tile_b: int = DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """code: (nb, block) int8, scale: (nb, 1) f32 -> (nb, block) f32."""
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.quantize_decode_ref(code, scale, bits)
    nb, block = code.shape
    tile_b = fit_tile(nb, tile_b)
    grid = (pl.cdiv(nb, tile_b),)
    return pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, block), lambda i: (i, 0)),
            pl.BlockSpec((tile_b, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=(backend == "interpret"),
    )(code, scale)

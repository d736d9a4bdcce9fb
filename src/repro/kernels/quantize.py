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


def row_tile(n_rows: int, width: int, cap: int = DEFAULT_TILE_B) -> int:
    """Rows per grid step for an (n_rows, width) operand whose rows hold
    ``width // DEFAULT_BLOCK`` quantization blocks each: ``cap`` rows of
    DEFAULT_BLOCK lanes' worth of VMEM, whatever the width, under
    fit_tile's rule.  At width 512 it is fit_tile(n_rows, cap); at 8192 it
    is 16 rows.  Widths up to MAX_WIDTH keep at least 8 rows a tile."""
    return fit_tile(n_rows, cap * DEFAULT_BLOCK // max(width, DEFAULT_BLOCK))


# the widest row that row_tile still gives 8 rows in the VMEM of a default
# (256, 512) tile
MAX_WIDTH = DEFAULT_TILE_B * DEFAULT_BLOCK // 8


def _encode_kernel(x_ref, u_ref, code_ref, scale_ref, *, bits: int,
                   block: int):
    if x_ref.shape[-1] == block:
        # one block a row: the flat engines' rows, their kernel unchanged
        x = x_ref[...]
        u = u_ref[...]
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        safe = jnp.where(scale > 0, scale, 1.0)
        lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(x) / safe + u)
        lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
        code_ref[...] = (jnp.sign(x) * lvl).astype(jnp.int8)
        scale_ref[...] = jnp.where(scale > 0, scale, 0.0).astype(jnp.float32)
        return
    # a row of k blocks: each lane segment of `block` quantizes on its own
    # scale, gathered into column j of the (tile, k) scale tile
    lane = jax.lax.broadcasted_iota(jnp.int32, scale_ref.shape, 1)
    scales = jnp.zeros(scale_ref.shape, jnp.float32)
    for j in range(x_ref.shape[-1] // block):
        seg = slice(j * block, (j + 1) * block)
        x = x_ref[:, seg]
        scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        safe = jnp.where(scale > 0, scale, 1.0)
        lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(x) / safe + u_ref[:, seg])
        lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
        code_ref[:, seg] = (jnp.sign(x) * lvl).astype(jnp.int8)
        scales = jnp.where(lane == j, jnp.where(scale > 0, scale, 0.0), scales)
    scale_ref[...] = scales


def _decode_kernel(code_ref, scale_ref, out_ref, *, bits: int, block: int):
    if code_ref.shape[-1] == block:
        code = code_ref[...].astype(jnp.float32)
        out_ref[...] = scale_ref[...] * (2.0 ** (1 - bits)) * code
        return
    scales = scale_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, scales.shape, 1)
    for j in range(code_ref.shape[-1] // block):
        seg = slice(j * block, (j + 1) * block)
        scale = jnp.sum(jnp.where(lane == j, scales, 0.0), axis=-1,
                        keepdims=True)
        code = code_ref[:, seg].astype(jnp.float32)
        out_ref[:, seg] = scale * (2.0 ** (1 - bits)) * code


def encode(x: jnp.ndarray, u: jnp.ndarray, *, bits: int = 2,
           block: Optional[int] = None, tile_b: int = DEFAULT_TILE_B,
           interpret: Optional[bool] = None):
    """x, u: (rows, width) f32, any rows; each row holds ``width // block``
    quantization blocks, one lane segment each (``block`` defaults to the
    width: one block a row).  ``tile_b`` caps the rows per grid step at
    DEFAULT_BLOCK lanes (see row_tile).

    Returns (code int8 (rows, width), scale f32 (rows, width // block))."""
    assert 1 <= bits <= 7, "int8 code container supports bits in [1, 7]"
    rows, width = x.shape
    block = width if block is None else block
    assert width % block == 0, (width, block)
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.quantize_encode_ref(x, u, bits, block)
    k = width // block
    tile = row_tile(rows, width, tile_b)
    row_spec = pl.BlockSpec((tile, width), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_encode_kernel, bits=bits, block=block),
        grid=(pl.cdiv(rows, tile),),
        in_specs=[row_spec, row_spec],
        out_specs=[row_spec, pl.BlockSpec((tile, k), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((rows, width), jnp.int8),
            jax.ShapeDtypeStruct((rows, k), jnp.float32),
        ],
        interpret=(backend == "interpret"),
        name="quantize_encode",
    )(x, u)


def decode(code: jnp.ndarray, scale: jnp.ndarray, *, bits: int = 2,
           tile_b: int = DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """code: (rows, width) int8, scale: (rows, k) f32 -> (rows, width) f32;
    each row holds k blocks of ``width // k`` lanes (k = 1: one a row)."""
    backend = resolve_backend(interpret)
    if backend == "jnp":
        from repro.kernels import ref
        return ref.quantize_decode_ref(code, scale, bits)
    rows, width = code.shape
    k = scale.shape[-1]
    tile = row_tile(rows, width, tile_b)
    row_spec = pl.BlockSpec((tile, width), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_decode_kernel, bits=bits, block=width // k),
        grid=(pl.cdiv(rows, tile),),
        in_specs=[row_spec, pl.BlockSpec((tile, k), lambda i: (i, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        interpret=(backend == "interpret"),
        name="quantize_decode",
    )(code, scale)

"""jit'd public wrappers around the Pallas kernels.

These handle the bookkeeping the kernels don't: flattening arbitrary arrays /
pytrees to the (n_blocks, block) layout, padding to tile multiples, dither
generation, and unpadding.  `interpret` defaults to None (auto): the jnp
reference math on CPU, compiled Pallas on TPU — see kernels/dispatch.py.
Pass interpret=True to force the true Pallas interpreter (the kernel-body
validation path), False to force compiled Pallas.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import lead_update as _lu
from repro.kernels import quantize as _q

DEFAULT_BLOCK = _q.DEFAULT_BLOCK


def _to_blocks(x: jnp.ndarray, block: int, tile_b: int):
    """Flatten + pad to (nb, block) with nb a multiple of tile_b."""
    flat = jnp.ravel(x).astype(jnp.float32)
    n = flat.shape[0]
    nb = -(-n // block)
    nb_pad = -(-nb // tile_b) * tile_b
    flat = jnp.pad(flat, (0, nb_pad * block - n))
    return flat.reshape(nb_pad, block), n


def _from_blocks(blocks: jnp.ndarray, n: int, shape, dtype):
    return jnp.ravel(blocks)[:n].reshape(shape).astype(dtype)


def _pick_tile(n_elements: int, block: int, tile_b: int) -> int:
    """Tile of the padded (nb, block) layout: tile_b halved (in multiples
    of 8) until it fits in nb, so padding stays bounded.  It obeys the
    kernels' tile rule (quantize.fit_tile): a multiple of 8 rows, or —
    under 8 blocks — the next power of two >= nb, which is then the whole
    padded row count."""
    nb = max(1, -(-n_elements // block))
    t = max(8, tile_b - tile_b % 8)
    while t > 8 and t > nb:
        t = max(8, t // 16 * 8)
    return t if t <= nb else 1 << (nb - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("bits", "block", "tile_b", "interpret"))
def quantize_encode(key, x: jnp.ndarray, *, bits: int = 2,
                    block: int = DEFAULT_BLOCK, tile_b: int = _q.DEFAULT_TILE_B,
                    interpret: Optional[bool] = None):
    """Quantize any-shape x; returns (code (nb, block) int8, scale (nb,1) f32).
    Blocks are the wire payload; decode with the original shape."""
    tile_b = _pick_tile(x.size, block, tile_b)
    xb, _ = _to_blocks(x, block, tile_b)
    u = jax.random.uniform(key, xb.shape, jnp.float32)
    return _q.encode(xb, u, bits=bits, tile_b=tile_b, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bits", "shape", "dtype", "tile_b", "interpret"))
def quantize_decode(code, scale, *, shape, bits: int = 2, dtype=jnp.float32,
                    tile_b: int = _q.DEFAULT_TILE_B, interpret: Optional[bool] = None):
    n = 1
    for s in shape:
        n *= int(s)
    tile_b = _pick_tile(code.size, code.shape[1], tile_b)
    vals = _q.decode(code, scale, bits=bits, tile_b=tile_b, interpret=interpret)
    return _from_blocks(vals, n, shape, dtype)


def quantize_roundtrip(key, x, *, bits: int = 2, block: int = DEFAULT_BLOCK,
                       interpret: Optional[bool] = None):
    """compress() semantics via the kernels (used by the kernel-backed
    Compressor in dist/trainer.py)."""
    code, scale = quantize_encode(key, x, bits=bits, block=block, interpret=interpret)
    return quantize_decode(code, scale, bits=bits, shape=tuple(x.shape),
                           dtype=jnp.dtype(x.dtype).name, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def lead_update_flat(x, g, d, h, hw, qh, wqh, eta, gamma, alpha, *,
                     tile_b: int = _q.DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """Fused LEAD post-comm update on flat 1-D vectors (any length)."""
    n = x.shape[0]
    tile_b = _pick_tile(n, DEFAULT_BLOCK, tile_b)
    blocks = [_to_blocks(a, DEFAULT_BLOCK, tile_b)[0] for a in (x, g, d, h, hw, qh, wqh)]
    outs = _lu.lead_update(*blocks, eta, gamma, alpha, tile_b=tile_b, interpret=interpret)
    return tuple(_from_blocks(o, n, (n,), x.dtype) for o in outs)


@functools.partial(jax.jit, static_argnames=("bits", "tile_b", "interpret"))
def lead_diff_encode_flat(key, x, g, d, h, eta, *, bits: int = 2,
                          tile_b: int = _q.DEFAULT_TILE_B, interpret: Optional[bool] = None):
    """Fused pre-comm pass on flat 1-D vectors; returns (code, scale)."""
    n = x.shape[0]
    tile_b = _pick_tile(n, DEFAULT_BLOCK, tile_b)
    xb, _ = _to_blocks(x, DEFAULT_BLOCK, tile_b)
    gb, _ = _to_blocks(g, DEFAULT_BLOCK, tile_b)
    db, _ = _to_blocks(d, DEFAULT_BLOCK, tile_b)
    hb, _ = _to_blocks(h, DEFAULT_BLOCK, tile_b)
    u = jax.random.uniform(key, xb.shape, jnp.float32)
    return _lu.lead_diff_encode(xb, gb, db, hb, u, eta, bits=bits,
                                tile_b=tile_b, interpret=interpret)


def pack_codes(code: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Pack b-bit signed codes (stored in int8 lanes) into dense uint32 lanes —
    the wire-accurate representation (32 // (bits+1) codes per uint32 word).

    A b-bit code c in [-(2^{b-1}), 2^{b-1}] is stored as a (bits+1)-bit
    two's-complement field (the extra bit carries the sign), so the wire
    accounting — QuantizePNorm.wire_bits and the roofline — charges
    (bits+1) bits per element, padded up to whole 32-bit words.
    Packing is a reshape + shift-or over int32 lanes (cheap on the VPU);
    `unpack_codes(pack_codes(c, b), n, b)` round-trips exactly
    (tests/test_kernels.py::test_pack_unpack_roundtrip_property).
    """
    width = bits + 1
    per32 = 32 // width
    flat = jnp.ravel(code).astype(jnp.int32) & ((1 << width) - 1)
    pad = (-flat.shape[0]) % per32
    flat = jnp.pad(flat, (0, pad))
    grp = flat.reshape(-1, per32)
    shifts = jnp.arange(per32, dtype=jnp.int32) * width
    return jnp.bitwise_or.reduce(grp << shifts[None, :], axis=1).astype(jnp.uint32)


def unpack_codes(packed: jnp.ndarray, n: int, bits: int) -> jnp.ndarray:
    width = bits + 1
    per32 = 32 // width
    shifts = jnp.arange(per32, dtype=jnp.int32) * width
    fields = (packed[:, None].astype(jnp.int32) >> shifts[None, :]) & ((1 << width) - 1)
    # sign-extend the width-bit field
    sign = 1 << (width - 1)
    vals = (fields ^ sign) - sign
    return jnp.ravel(vals)[:n].astype(jnp.int8)

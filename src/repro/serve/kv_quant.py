"""KV-page codec: the LEAD wire quantizer applied to KV-cache pages.

A KV page holds ``page`` token positions of one layer's K (or V):
``page * kv_heads * head_dim`` contiguous elements.  Flattened page-major,
a pool of pages is a ``(n_pages, nb, block)`` buffer — exactly the flat
wire layout of ``kernels/quantize.py`` — so cold pages are stored as int8
codes + one f32 scale per block and decoded on read with the same fused
kernels that move LEAD's payloads.

Two deliberate departures from the wire path:

* **Deterministic half-dither** (``u = 0.5``): the wire uses stochastic
  dither for unbiasedness across iterations; a cache is written once and
  read many times, so round-to-nearest (floor(q + 0.5)) minimizes the
  per-read error and keeps serving bit-reproducible with no RNG state in
  the cache.
* **Bits/elem accounting mirrors ``QuantizePNorm.wire_bits``**: each
  element costs ``bits + 1`` bits (sign rides along) plus one 32-bit scale
  per block — ``(bits+1) + 32/block`` bits/elem.  The int8 code container
  is an implementation detail, exactly as on the wire (``ops.pack_codes``
  is the pure-reshape packing to dense words).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import quantize as _q


def pick_block(elems_per_page: int, target: int = _q.DEFAULT_BLOCK) -> int:
    """Largest power-of-two-ish divisor of elems_per_page <= target (the
    codec needs block | elems so a page is a whole number of blocks)."""
    block = min(target, elems_per_page)
    while elems_per_page % block:
        block -= 1
    return block


@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Static codec parameters for one pool (hashable pytree aux data)."""
    bits: int
    block: int

    def __post_init__(self):
        assert 1 <= self.bits <= 7, "int8 code container supports bits in [1, 7]"

    @property
    def bits_per_elem(self) -> float:
        """Wire-meter bits per cached element: (b+1)-bit code + the f32
        block scale amortized over the block."""
        return (self.bits + 1) + 32.0 / self.block

    def page_bits(self, elems_per_page: int) -> int:
        """Exact meter for one page (mirrors QuantizePNorm.wire_bits)."""
        nb = elems_per_page // self.block
        return elems_per_page * (self.bits + 1) + nb * 32


def encode_rows(x: jnp.ndarray, spec: KVQuantSpec,
                interpret: Optional[bool] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (R, *page_shape) -> codes (R, nb, block) int8, scales (R, nb, 1).

    R is any leading row count (a batch of pages); the page payload is
    flattened to whole codec blocks and quantized with deterministic
    half-dither (round-to-nearest)."""
    R = x.shape[0]
    elems = int(x.size) // max(R, 1)
    nb = elems // spec.block
    assert nb * spec.block == elems, (elems, spec.block)
    xb = x.astype(jnp.float32).reshape(R * nb, spec.block)
    u = jnp.full(xb.shape, 0.5, jnp.float32)
    code, scale = _q.encode(xb, u, bits=spec.bits, interpret=interpret)
    return code.reshape(R, nb, spec.block), scale.reshape(R, nb, 1)


def decode_rows(code: jnp.ndarray, scale: jnp.ndarray, spec: KVQuantSpec,
                page_shape: Tuple[int, ...], dtype,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """codes (..., nb, block) + scales (..., nb, 1) -> (..., *page_shape)."""
    lead = code.shape[:-2]
    R = 1
    for s in lead:
        R *= int(s)
    nb = code.shape[-2]
    vals = _q.decode(code.reshape(R * nb, spec.block),
                     scale.reshape(R * nb, 1), bits=spec.bits,
                     interpret=interpret)
    return vals.reshape(*lead, *page_shape).astype(dtype)

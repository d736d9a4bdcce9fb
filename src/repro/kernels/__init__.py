"""Pallas TPU kernels for the LEAD hot path.

quantize:     blockwise inf-norm b-bit stochastic quantization (paper Thm 3)
lead_update:  fused LEAD state update + fused diff-encode (Alg. 1 lines 4-7)
sparsify:     fused RandK (shared-seed mask) / TopK (threshold+mask) encodes
ops:          jit'd public wrappers (padding, dither, pytree plumbing)
dispatch:     backend resolution (interpret vs compiled Pallas)
ref:          pure-jnp oracles the tests assert against

Backend dispatch contract
-------------------------
Every kernel entry point takes ``interpret`` as a tri-state, resolved by
dispatch.resolve_backend to one of three backends:

    interpret=None (default)  auto-dispatch: the ``jnp`` backend on CPU
                              (kernel semantics via the ref.py math, fused
                              by XLA — the fast CPU execution), compiled
                              ``pallas`` on TPU/GPU.
    interpret=True            the true Pallas interpreter — slow bit-level
                              emulation of the kernel bodies; what the
                              kernel test-suite pins to validate them.
    interpret=False           force compiled Pallas (real accelerators).

``REPRO_KERNEL_BACKEND=jnp|interpret|pallas`` overrides the auto decision.
Callers (core/engine.py, core/simulator.py, benchmarks) should pass the
tri-state through rather than hardcoding a bool.

Flat block layout contract
--------------------------
All kernels operate on the blockified layout produced by ops._to_blocks:
a logical f32 vector of length d is zero-padded and reshaped to
``(nb, block)`` with ``block = 512`` (the paper's quantization block,
4 x 128 TPU lanes) and any ``nb``: each kernel takes ``quantize.fit_tile``
rows per grid step — the whole array, or 256 rows with a ragged last
block — because Mosaic refuses a block whose row count is neither a
multiple of 8 nor the whole array's.  Rows are independent quantization
blocks, so batched callers (the flat
engine family in core/engines/ — LEAD plus the flat twins of every paper
baseline) may stack agents along the row axis — ``(n_agents * nb, block)``
— and make a single kernel call.  Zero rows are a fixed point of every
kernel (codes/scales/updates stay zero), which is what makes the
zero-padding safe.  The family's shared substrate
(core/engines/base.py: blockify/unblockify, the dither plane, the
encode/decode wire stage, dense|ring gossip) is the single producer of
buffers in this layout; every engine state is a NamedTuple of such
buffers.

Encoded-payload interface (codes on the wire)
---------------------------------------------
Every compressor exposes a flat wire path over the same blocked layout
(core/compression.py): ``encode_blocks(key, (n, nb, block), dim) ->
(payload, bits)`` / ``decode_blocks(payload)``.  The payload is the ONLY
thing that may cross agents — the gossip stages (dist/trainer.py's
per-round ppermute exchange on mesh axes, core/gossip.py
EncodedNeighborGossip on the flat agent axis) move payload leaves between
agents and decode at the receiver, and `bits` is the per-agent wire cost
of the actual payload.  The kernels here are the fused producers of those
payloads:

    QuantizePNorm(p=inf)  LEAD's fused diff+encode is
                          lead_update.lead_diff_encode; the baseline engines
                          (CHOCO/DeepSqueeze/QDGD/DCD hat-difference
                          updates) feed their message buffer through
                          quantize.encode with the same dither plane ->
                          {code int8 (rows, block), scale f32 (rows, 1)};
                          quantize.decode at the receiver; ops.pack_codes
                          turns the int8 lanes into the dense (bits+1)-bit
                          uint32 wire words.
    RandK                 sparsify.randk_encode -> {values f32}: keep-mask
                          u < ratio computed in-kernel from the shared-seed
                          dither plane; no indices travel.  Reused as-is by
                          the baseline engines' difference compression.
    TopK                  sparsify.mask_apply  -> {values f32}: applies the
                          exact-k mask built from jax.lax.top_k indices
                          (ties must not inflate the payload past the k
                          values the accounting charges), or — with
                          approx_threshold — the sampled-quantile mask
                          (O(d/block) threshold, data-dependent bits).
"""
from repro.kernels import dispatch, ops, ref, sparsify
from repro.kernels.dispatch import default_backend, resolve_backend
from repro.kernels.ops import (
    lead_diff_encode_flat, lead_update_flat, pack_codes, quantize_decode,
    quantize_encode, quantize_roundtrip, unpack_codes,
)
from repro.kernels.sparsify import mask_apply, randk_encode

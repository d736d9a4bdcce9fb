"""Communication-compression operators (paper §5 / Appendix C).

The paper's workhorse is the unbiased p-norm b-bit stochastic quantizer
(Theorem 3):

    Q_p(x) = (||x||_p * sign(x) * 2^{-(b-1)}) .* floor( 2^{b-1} |x| / ||x||_p + u )

with u ~ Uniform[0,1]^d.  It is unbiased and its variance is bounded by
(1/4) * 2^{-2(b-1)} * d_block * ||x||_p^2, which is minimized by p = inf
(Theorem 3: ||x||_p <= ||x||_q for q <= p).  The paper applies it *blockwise*
with block = 512, b = 2.

Every operator implements the `Compressor` protocol:

    compress(key, x)      -> xhat               (the decoded estimate; the
                                                 simulator path and the LEAD
                                                 algebra only need xhat)
    encode(key, x)        -> (payload, spec)    payload: pytree of arrays (the
                                                 wire representation), spec:
                                                 static metadata (shapes etc.)
    decode(payload, spec) -> xhat
    wire_bits(n_elements) -> float               static bits-on-the-wire
                                                 estimate for d elements
    variance_constant(d)  -> C bound from Assumption 2 (if known)

plus the *flat-layout wire path* used by the flat LEAD engine
(core/engine.py) and the distributed trainer (dist/trainer.py), operating on
the kernels' blocked ``(n_agents, nb, block)`` f32 buffers (zero-padded past
the logical per-agent dimension ``dim``).  An operator whose blocks are
contiguous lane segments says so with ``row_segments = True`` (QuantizePNorm,
Identity) and also takes ``(n_agents, rows, k * block)`` buffers, rows *
k * block == dim, block j of a row being its lane segment j: the trainer's
tiled view of a model leaf (kernels/__init__.py).  Its scales are then
``(n_agents, rows, k)``.

    encode_blocks(key, buf, dim, interpret=None) -> (payload, bits)
        payload: dict of arrays with leading agent axis n — exactly what
        crosses agents in encoded gossip (the trainer's per-round ppermute
        exchange; EncodedNeighborGossip models it on the flat agent axis);
        nothing outside the payload may travel.
        bits: scalar f32, bits per agent actually on the wire THIS step,
        computed from the payload (for RandK this is data-dependent).
    decode_blocks(payload, interpret=None) -> the buffer's shape, f32
        decoded estimate.

A ``row_segments`` operator also has ``encode_agents(keys, buf, dim,
interpret=None)``: encode_blocks with each agent's own key given, ``keys``
being the (n,) keys encode_blocks splits from its one key.  Each agent's
payload then depends on its own key and buffer alone, so the trainer runs
it inside its shard_map on each device's own agents (XLA cannot partition
the quantize kernel over the agent axis).

The shared-randomness contract: encode_blocks splits `key` into one key per
agent exactly like simulator.vmap_compress does, so flat-engine trajectories
match the per-agent tree path draw for draw.  RandK's payload contains only
the kept *values* — the mask is reproducible from the shared per-agent seed,
so no indices travel (paper App. C.2).  TopK must ship indices; its bits
charge k * (32 + log2 d).

Unbiasedness (Assumption 2) is property-tested in tests/test_compression.py.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.utils.tree import Pytree


def rel_err(q: jnp.ndarray, target: jnp.ndarray,
            ref: jnp.ndarray) -> jnp.ndarray:
    """||q - target|| / ||ref||: relative compression error of a transmitted
    message `target` with estimate `q`, normalized by the pre-communication
    iterate `ref` that carries it.  The single source of the Trace comp_err
    convention (core/simulator.py), shared by the tree baselines
    (core/baselines.py) and the flat engine family (core/engines/) so their
    traces stay comparable to 1e-5."""
    return (jnp.linalg.norm(jnp.ravel(q - target))
            / (jnp.linalg.norm(jnp.ravel(ref)) + 1e-12))


def _block_view(x: jnp.ndarray, block: int):
    """Pad a flattened array to a multiple of `block` and reshape to (nb, block)."""
    flat = jnp.ravel(x)
    n = flat.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(nb, block), n


def _unblock(blocks: jnp.ndarray, n: int, shape):
    return jnp.reshape(jnp.ravel(blocks)[:n], shape)


def _is_inf(p) -> bool:
    return p == jnp.inf or p == math.inf or p == "inf"


def _pnorm(x: jnp.ndarray, p, axis=-1, keepdims=True):
    if _is_inf(p):
        return jnp.max(jnp.abs(x), axis=axis, keepdims=keepdims)
    return jnp.sum(jnp.abs(x) ** p, axis=axis, keepdims=keepdims) ** (1.0 / p)


def _stochastic_quantize(blocks: jnp.ndarray, u: jnp.ndarray, bits: int, p):
    """The paper's p-norm b-bit stochastic quantize step (Thm 3), blockwise
    over the LAST axis.  Single source of truth for the tree (encode) and
    flat (encode_blocks) wire paths — they must stay formula-identical for
    the flat/tree trajectory-equivalence contract.

    Returns (code int8, scale f32), shapes (..., block) / (..., 1)."""
    blocks = blocks.astype(jnp.float32)
    scale = _pnorm(blocks, p)
    safe = jnp.where(scale > 0, scale, 1.0)
    lvl = jnp.floor((2.0 ** (bits - 1)) * jnp.abs(blocks) / safe + u)
    # levels live in [0, 2^{b-1}]  (inclusive upper end reachable when
    # |x| == scale and u -> 1), which fits b bits alongside the sign.
    lvl = jnp.minimum(lvl, 2.0 ** (bits - 1))
    code = (jnp.sign(blocks) * lvl).astype(jnp.int8)
    return code, jnp.where(scale > 0, scale, 0.0).astype(jnp.float32)


def _nb_logical(dim: int, block: int) -> int:
    return -(-dim // block)


def _flat_to_rows(buf: jnp.ndarray, dim: int):
    """(n, nb, block) -> (n, dim): drop the zero padding past the logical dim."""
    n = buf.shape[0]
    return buf.reshape(n, -1)[:, :dim]


def _rows_to_flat(rows: jnp.ndarray, like: jnp.ndarray):
    """(n, dim) -> (n, nb, block) zero-padded to `like`'s blocked shape."""
    n, nb, block = like.shape
    pad = nb * block - rows.shape[1]
    return jnp.pad(rows, ((0, 0), (0, pad))).reshape(n, nb, block)


@dataclasses.dataclass(frozen=True)
class QuantizePNorm:
    """Unbiased blockwise p-norm b-bit stochastic quantizer (paper Thm 3).

    bits:  total bits per element for the integer code (paper uses 2).
    p:     norm order; inf is the paper's choice.
    block: block size for the blockwise application (paper uses 512).
    """
    bits: int = 2
    p: float = math.inf
    block: int = 512

    def __post_init__(self):
        # codes live in [-(2^{b-1}), 2^{b-1}] and are stored in int8 lanes:
        # bits <= 7 keeps the top level representable (the paper uses 2).
        assert 1 <= self.bits <= 7, "int8 code container supports bits in [1, 7]"

    # -- simulator path ----------------------------------------------------
    def compress(self, key, x: jnp.ndarray) -> jnp.ndarray:
        payload, spec = self.encode(key, x)
        return self.decode(payload, spec)

    # -- wire path ----------------------------------------------------------
    def encode(self, key, x: jnp.ndarray):
        blocks, n = _block_view(x, self.block)
        u = jax.random.uniform(key, blocks.shape, jnp.float32)
        code, scale = _stochastic_quantize(blocks, u, self.bits, self.p)
        payload = {"code": code, "scale": scale}
        spec = {"n": n, "shape": x.shape, "dtype": jnp.dtype(x.dtype).name}
        return payload, spec

    def decode(self, payload: dict, spec: dict) -> jnp.ndarray:
        b = self.bits
        vals = payload["scale"] * (2.0 ** (1 - b)) * payload["code"].astype(jnp.float32)
        out = _unblock(vals, spec["n"], spec["shape"])
        return out.astype(spec["dtype"])

    def wire_bits(self, n_elements: int) -> float:
        # b bits of code per element (sign + level fit in b bits for the
        # b-bit quantizer: level in [0, 2^{b-1}]) + one f32 scale per block.
        nb = -(-n_elements // self.block)
        return n_elements * (self.bits + 1) + nb * 32  # +1: sign bit

    # -- flat-layout wire path (engine / dist trainer) ----------------------
    # encode_blocks / decode_blocks also take (n, rows, k * block) buffers,
    # block j of a row being its lane segment j (the trainer's tiled leaf
    # view, dist/trainer.py)
    row_segments = True

    def encode_blocks(self, key, buf: jnp.ndarray, dim: int,
                      interpret: Optional[bool] = None):
        """buf: (n, nb, block) f32, zero-padded past dim, or (n, rows, k *
        block) with rows * k * block == dim.  Per-agent dither is drawn
        exactly as the tree path does (split key, uniform over the logical
        (ceil(dim/block), block) block matrix; a (rows, k * block) draw is
        the same numbers, as the threefry counter is the row-major index),
        so the payload matches vmap_compress + encode draw for draw.  (The
        p=inf engine hot path uses the fused lead_diff_encode kernel
        instead; this generic path serves p != inf and the dist trainer,
        where XLA fuses it, and rows of k blocks take the quantize kernel,
        which reduces each lane segment in place.)"""
        return self.encode_agents(jax.random.split(key, buf.shape[0]), buf,
                                  dim, interpret=interpret)

    def encode_agents(self, keys, buf: jnp.ndarray, dim: int,
                      interpret: Optional[bool] = None):
        """encode_blocks with agent i's dither drawn from ``keys[i]``."""
        n, rows, width = buf.shape
        block = self.block
        assert width % block == 0, (width, block)
        nbl = _nb_logical(dim, block)
        if width == block:
            u = jax.vmap(lambda kk: jax.random.uniform(
                kk, (nbl, block), jnp.float32))(keys)
            u = jnp.pad(u, ((0, 0), (0, rows - nbl), (0, 0)))
            code, scale = _stochastic_quantize(buf, u, self.bits, self.p)
        else:
            assert rows * width == dim, (buf.shape, dim)
            k = width // block
            u = jax.vmap(lambda kk: jax.random.uniform(
                kk, (rows, width), jnp.float32))(keys)
            if _is_inf(self.p):
                from repro.kernels import quantize as _q
                code, scale = _q.encode(
                    buf.reshape(n * rows, width), u.reshape(n * rows, width),
                    bits=self.bits, block=block, interpret=interpret)
            else:
                segs = lambda a: a.reshape(n, rows, k, block)
                code, scale = _stochastic_quantize(segs(buf), segs(u),
                                                   self.bits, self.p)
            code, scale = code.reshape(buf.shape), scale.reshape(n, rows, k)
        payload = {"code": code, "scale": scale}
        # actual payload: (b+1)-bit codes for the dim logical elements + one
        # f32 scale per logical block (the padded tail rows never travel).
        bits = jnp.asarray(dim * (self.bits + 1) + nbl * 32, jnp.float32)
        return payload, bits

    def decode_blocks(self, payload: dict,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
        code, scale = payload["code"], payload["scale"]
        if scale.shape[-1] == 1:
            return scale * (2.0 ** (1 - self.bits)) * code.astype(jnp.float32)
        from repro.kernels import quantize as _q
        width = code.shape[-1]
        rows = _q.decode(code.reshape(-1, width),
                         scale.reshape(-1, scale.shape[-1]), bits=self.bits,
                         interpret=interpret)
        return rows.reshape(code.shape)

    def variance_constant(self, d_block: Optional[int] = None) -> float:
        """Upper bound on C in  E||x - Q(x)||^2 <= C ||x||^2  (Remark 7).

        For p=inf and blockwise application, ||x||_inf <= ||x||_2 per block so
        C <= d_block * 2^{-2(b-1)} / 4.
        """
        d = d_block if d_block is not None else self.block
        return d * (2.0 ** (-2 * (self.bits - 1))) / 4.0


@dataclasses.dataclass(frozen=True)
class TopK:
    """Biased top-k sparsifier (used in the Fig. 6 compression-error study).

    ratio: fraction of entries kept.  Index transmission costs log2(d) bits
    per kept entry (no shared-seed trick possible).

    Exactly k entries are kept: the mask comes from jax.lax.top_k *indices*
    (a magnitude threshold `|x| >= kth` would keep every tied entry, sending
    more than the k values wire_bits charges).

    approx_threshold=True switches the *flat* path (encode_blocks) to a
    sampled-quantile threshold: instead of a per-agent lax.top_k over all d
    elements (O(d log d), the dominant cost of the flat TopK step — see
    bench_lead_step/step_flat_topk*), each agent draws sample_per_block
    random elements per logical block (m = sample_per_block * ceil(d/block)
    total, O(d/block) per block) and keeps everything at or above the
    sample's ratio-quantile.  The kept count is then only approximately k,
    so the payload bits become data-dependent (counted from the actual
    mask); the decoded estimate keeps the largest entries with high
    probability.  The tree path (compress/encode) always stays exact-k.
    """
    ratio: float = 0.1
    approx_threshold: bool = False
    sample_per_block: int = 8

    def _k(self, d: int) -> int:
        return max(1, int(d * self.ratio))

    def _mask_rows(self, rows: jnp.ndarray) -> jnp.ndarray:
        """(n, d) -> boolean keep-mask with exactly k True per row."""
        n, d = rows.shape
        _, idx = jax.lax.top_k(jnp.abs(rows), self._k(d))
        return (jnp.zeros((n, d), bool)
                .at[jnp.arange(n)[:, None], idx].set(True))

    def compress(self, key, x: jnp.ndarray) -> jnp.ndarray:
        del key
        flat = jnp.ravel(x)
        mask = self._mask_rows(flat[None])[0]
        return jnp.reshape(jnp.where(mask, flat, 0.0), x.shape)

    def encode(self, key, x):
        return {"dense": self.compress(key, x)}, {}

    def decode(self, payload, spec):
        return payload["dense"]

    def wire_bits(self, n_elements: int) -> float:
        k = self._k(n_elements)
        return k * (32 + math.log2(max(n_elements, 2)))

    def _approx_mask_rows(self, key, rows: jnp.ndarray) -> jnp.ndarray:
        """(n, d) -> keep-mask from a sampled-quantile threshold: per agent,
        sample m = sample_per_block * ceil(d/block) random magnitudes, take
        the (k*m/d)-th largest as the threshold, keep |x| >= threshold.
        O(m log m) instead of O(d log d) — the kept count is ~k, not exact."""
        from repro.kernels.quantize import DEFAULT_BLOCK
        n, d = rows.shape
        m = min(self.sample_per_block * _nb_logical(d, DEFAULT_BLOCK), d)
        rank = min(max(1, round(self._k(d) * m / d)), m)
        idx = jax.random.randint(key, (n, m), 0, d)
        sample = jnp.abs(jnp.take_along_axis(rows, idx, axis=1))
        thr = jax.lax.top_k(sample, rank)[0][:, -1:]
        a = jnp.abs(rows)
        # strict-positive guard: an all-zero sample row must not keep the
        # whole (zero) vector and charge d entries of wire traffic for it
        return (a >= thr) & (a > 0.0)

    # -- flat-layout wire path ----------------------------------------------
    def encode_blocks(self, key, buf: jnp.ndarray, dim: int,
                      interpret: Optional[bool] = None):
        """Threshold+mask over the logical rows: per-agent keep-mask applied
        by the fused kernels.sparsify.mask_apply pass; payload = masked
        values in block layout (kept values + indices on the wire; the dense
        zeros are layout, not traffic).

        Exact mode (default) builds the mask from top_k indices (exactly k
        kept, static wire bits); approx_threshold=True uses the sampled
        quantile above — data-dependent kept count, bits counted from the
        actual mask."""
        from repro.kernels.sparsify import mask_apply
        n, nb, block = buf.shape
        rows = _flat_to_rows(buf, dim)
        if self.approx_threshold:
            maskr = self._approx_mask_rows(key, rows)
            bits = jnp.mean(jnp.sum(maskr.astype(jnp.float32), axis=1)) \
                * (32.0 + math.log2(max(dim, 2)))
        else:
            maskr = self._mask_rows(rows)
            bits = jnp.asarray(self.wire_bits(dim), jnp.float32)
        mask = _rows_to_flat(maskr.astype(jnp.float32), buf)
        vals = mask_apply(buf.reshape(n * nb, block),
                          mask.reshape(n * nb, block), interpret=interpret)
        payload = {"values": vals.reshape(n, nb, block)}
        return payload, bits

    def decode_blocks(self, payload: dict,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
        del interpret
        return payload["values"]

    def variance_constant(self, d_block=None):
        return None  # biased: Assumption 2 does not hold


@dataclasses.dataclass(frozen=True)
class RandK:
    """Unbiased random-k sparsifier: keep a random fraction, rescale by 1/ratio.

    With a shared PRNG seed, indices need not be transmitted (paper App. C.2).
    """
    ratio: float = 0.1
    rescale: bool = True

    def compress(self, key, x: jnp.ndarray) -> jnp.ndarray:
        mask = jax.random.bernoulli(key, self.ratio, x.shape)
        scale = (1.0 / self.ratio) if self.rescale else 1.0
        return jnp.where(mask, x * scale, 0.0).astype(x.dtype)

    def encode(self, key, x):
        return {"dense": self.compress(key, x)}, {}

    def decode(self, payload, spec):
        return payload["dense"]

    def wire_bits(self, n_elements: int) -> float:
        return n_elements * self.ratio * 32

    # -- flat-layout wire path ----------------------------------------------
    def encode_blocks(self, key, buf: jnp.ndarray, dim: int,
                      interpret: Optional[bool] = None):
        """Shared-seed mask: the per-agent keep-mask u < ratio is
        reproducible from `key` on both sides of the wire, so the payload is
        values-only (no indices travel — paper App. C.2).  The mask-and-scale
        is the fused kernels.sparsify.randk_encode pass (the mask never
        round-trips to memory).  Bits are data-dependent: 32 per
        actually-kept entry, averaged over agents.

        The per-agent dither draw matches the tree path's
        jax.random.bernoulli(key_i, ratio, (dim,)) — bernoulli IS
        uniform(key) < p — so flat and tree trajectories coincide."""
        from repro.kernels.sparsify import randk_encode
        n, nb, block = buf.shape
        keys = jax.random.split(key, n)
        u = jax.vmap(lambda kk: jax.random.uniform(
            kk, (dim,), jnp.float32))(keys)
        # pad with 1.0 (>= ratio): the layout tail is never kept
        u_blocks = jnp.pad(u, ((0, 0), (0, nb * block - dim)),
                           constant_values=1.0)
        vals = randk_encode(buf.reshape(n * nb, block),
                            u_blocks.reshape(n * nb, block), ratio=self.ratio,
                            rescale=self.rescale, interpret=interpret)
        payload = {"values": vals.reshape(n, nb, block)}
        bits = jnp.mean(jnp.sum((u < self.ratio).astype(jnp.float32),
                                axis=1)) * 32.0
        return payload, bits

    def decode_blocks(self, payload: dict,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
        del interpret
        return payload["values"]

    def variance_constant(self, d_block=None):
        # E||x - Q(x)||^2 = (1/ratio - 1)||x||^2 for the rescaled variant.
        return 1.0 / self.ratio - 1.0


@dataclasses.dataclass(frozen=True)
class Identity:
    """No compression (C = 0); LEAD reduces to NIDS with gamma=1."""

    def compress(self, key, x):
        del key
        return x

    def encode(self, key, x):
        return {"dense": x}, {}

    def decode(self, payload, spec):
        return payload["dense"]

    def wire_bits(self, n_elements: int) -> float:
        return n_elements * 32

    # -- flat-layout wire path ----------------------------------------------
    row_segments = True                  # any buffer shape: the raw values

    def encode_blocks(self, key, buf: jnp.ndarray, dim: int,
                      interpret: Optional[bool] = None):
        del key, interpret
        return {"values": buf}, jnp.asarray(dim * 32, jnp.float32)

    encode_agents = encode_blocks        # no key is drawn from

    def decode_blocks(self, payload: dict,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
        del interpret
        return payload["values"]

    def variance_constant(self, d_block=None):
        return 0.0


# -- pytree lifting ---------------------------------------------------------

def compress_pytree(compressor, key, tree: Pytree) -> Pytree:
    """Apply a compressor leaf-wise to a pytree with split keys."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = [compressor.compress(k, l) for k, l in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def estimate_C(compressor, key, d=4096, trials=64, dtype=jnp.float32) -> float:
    """Monte-Carlo estimate of the contraction constant C (Assumption 2)."""
    def one(k):
        kx, kq = jax.random.split(k)
        x = jax.random.normal(kx, (d,), dtype)
        xh = compressor.compress(kq, x)
        return jnp.sum((x - xh) ** 2) / jnp.sum(x ** 2)
    vals = jax.vmap(one)(jax.random.split(key, trials))
    return float(jnp.max(vals))

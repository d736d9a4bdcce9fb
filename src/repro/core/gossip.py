"""Gossip (decentralized mixing) backends.

Interchangeable implementations of `mix` over the Topology API
(core/topology.py):

* DenseGossip — explicit mixing-matrix multiply.  The reference/simulator
  path: states carry a leading agent dimension `n` on a single device.
  Accepts a Topology or a raw matrix.
* EncodedNeighborGossip — sparse neighbor exchange on the leading agent
  axis, built from a Topology's padded ``neighbors``/``weights`` table:
  each agent combines its own decoded payload with a *gather* of its
  neighbors' — O(n * deg * d) where the dense mix is O(n^2 * d), and valid
  for ANY Assumption-1 graph (ring, torus, Erdős–Rényi, ...).  The payload
  is decoded exactly once: per-agent decode commutes with the neighbor
  gather, so decoding before the (virtual) exchange is numerically
  identical to decoding at every receiver — the wire model (only the
  payload crosses agents, bits read off the actual payload) is unchanged,
  without the old 3x receiver decode.
* RingGossip — `jax.lax.ppermute` over one or more mesh axes.  The
  production path: must be called *inside* a (partial-manual) shard_map whose
  manual axes are exactly `axes`.  The ring is laid out over the flattened
  mesh axes so that consecutive neighbors are intra-pod except at the two
  pod-boundary edges — the compressed payload is the only traffic that
  crosses pods.  Arbitrary graphs reach the multi-host path through
  ``Topology.permute_rounds()`` (dist/trainer.py), not through this class.
* HierarchicalGossip — two-level mixing for ``topology.hierarchical``
  graphs: exact (free) intra-node block averaging + EncodedNeighborGossip
  over the inter-node graph, so only node-mean payloads pay wire bits.
* EncodedRingGossip — the uniform-ring special case of
  EncodedNeighborGossip, kept for its (w_self, w_neighbor) reading API.

All back-ends operate on pytrees leaf-wise.  DenseGossip and
EncodedNeighborGossip additionally expose ``mix_masked`` — the degraded
mixing path under a core/faults.py link-survival mask (renormalized
surviving weights, or stale-cache substitution for dropped links) — used
by the engines' fault-injection layer (engines/base.py
``mix_payload_faulted``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.utils.tree import Pytree, tree_map

# Every dense mix by W runs at full f32 precision.  A TPU's default f32
# matmul is one bf16 pass, which rounds the ring's weights 1/3 to 0.333984:
# rows then sum to 1.002, W stops being doubly stochastic, and LEAD's dual
# no longer sums to zero, which moves its fixed point off x*.
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class DenseGossip:
    """mix(X) = W @ X along the leading agent axis (simulator path).

    W may be a core/topology.Topology (unwrapped to its dense matrix in
    __post_init__) or any (n, n) array."""
    W: Any  # (n, n) array

    def __post_init__(self):
        # unwrap a Topology to its dense matrix (duck-typed: topology.py
        # must stay importable without this module).  A TopologyBank also
        # matches and unwraps to its round-0 matrix — the init-time mixing
        # convention; per-step bank mixing goes through ``for_round``.
        if hasattr(self.W, "neighbors") and hasattr(self.W, "W"):
            object.__setattr__(self, "W", self.W.W)

    @staticmethod
    def for_round(bank, k) -> "DenseGossip":
        """The step-k dense backend of a topology.TopologyBank: slice the
        stacked (P, n, n) matrices at the *traced* index ``k % P``.  The
        slice is a gather inside the jitted step — the graph changes every
        iteration of one compiled scan, no retracing."""
        r = jnp.asarray(k, jnp.int32) % bank.period
        return DenseGossip(W=jnp.asarray(bank.Ws, jnp.float32)[r])

    @property
    def n(self) -> int:
        return self.W.shape[0]

    def mix(self, tree: Pytree) -> Pytree:
        W = jnp.asarray(self.W)

        def one(x):
            return jnp.tensordot(W.astype(x.dtype), x, axes=([1], [0]),
                                 precision=HIGHEST)

        return tree_map(one, tree)

    def i_minus_w(self, tree: Pytree) -> Pytree:
        mixed = self.mix(tree)
        return tree_map(jnp.subtract, tree, mixed)

    def mix_masked(self, x: jnp.ndarray, mask: jnp.ndarray, *,
                   x_tx: jnp.ndarray = None,
                   cache: jnp.ndarray = None) -> jnp.ndarray:
        """Degraded ``W @ x`` under a link-survival mask (core/faults.py):
        ``mask[i, j]`` says whether link i <- j delivered this step (the
        diagonal must be True).  With ``cache=None`` the surviving row
        weights are renormalized — dropped mass reassigned to the self
        weight, so realized rows stay stochastic (and symmetric masks stay
        doubly stochastic; isolated rows degenerate to self-weight 1.0,
        see faults.renormalize_dense); with a cache buffer, dropped links
        are served at full weight from the sender's last successful
        broadcast (stale policy).  ``x_tx`` is the buffer
        as transmitted (bit-flip corruption applies to the wire copy);
        the self column always uses the clean local ``x``.  Operates on a
        single (n, ...) buffer — the engines' blocked payloads — not a
        pytree."""
        from repro.core import faults as faults_mod
        W = jnp.asarray(self.W, x.dtype)
        n = W.shape[0]
        x_tx = x if x_tx is None else x_tx
        eye = jnp.eye(n, dtype=x.dtype)
        shape = (-1,) + (1,) * (x.ndim - 1)

        def matmul(M, b):
            return jnp.matmul(M, b.reshape(n, -1),
                              precision=HIGHEST).reshape(b.shape)

        if cache is None:
            Wr = faults_mod.renormalize_dense(W, mask)
            own = jnp.diagonal(Wr).reshape(shape) * x
            return own + matmul(Wr * (1.0 - eye), x_tx)
        off = W * (1.0 - eye)
        own = jnp.diagonal(W).reshape(shape) * x
        return (own + matmul(off * mask, x_tx)
                + matmul(off * ~mask, cache))


@dataclasses.dataclass(frozen=True)
class EncodedNeighborGossip:
    """Sparse neighbor-exchange mixing on the leading (agent) axis.

    Built from a Topology's padded table: ``neighbors`` (n, deg_max) int
    indices (self-padded) and ``weights`` (n, deg_max + 1) with the self
    weight in column 0 (padding weights 0.0).  ``mix`` computes, per leaf,

        out[i] = weights[i, 0] * x[i] + sum_j weights[i, 1+j] * x[nbr[i, j]]

    — exactly ``W @ x`` up to summation order, in O(n * deg * d) instead of
    the dense O(n^2 * d).  This is the single-device model of multi-host
    neighbor exchange (``Topology.permute_rounds`` + ppermute in
    dist/trainer.py): only the encoded payload conceptually crosses agents,
    and since per-agent decode commutes with the gather, the receiver's
    decode is hoisted before the exchange and runs ONCE per step (the old
    EncodedRingGossip decoded own + both rolled copies — 3x).
    """
    neighbors: Any                       # (n, deg_max) int
    weights: Any                         # (n, deg_max + 1) float

    @staticmethod
    def from_topology(topo) -> "EncodedNeighborGossip":
        return EncodedNeighborGossip(neighbors=topo.neighbors,
                                     weights=topo.weights)

    @staticmethod
    def for_round(bank, k) -> "EncodedNeighborGossip":
        """The step-k sparse backend of a topology.TopologyBank: slice the
        stacked (P, n, max_deg) tables at the *traced* index ``k % P``.
        The bank's shared layout keeps ``deg_max`` static, so ``mix``'s
        column-at-a-time loop unrolls exactly as in the static case —
        still O(n * deg * d), still decode-once."""
        r = jnp.asarray(k, jnp.int32) % bank.period
        return EncodedNeighborGossip(
            neighbors=jnp.asarray(bank.neighbors)[r],
            weights=jnp.asarray(bank.weights, jnp.float32)[r])

    def mix(self, tree: Pytree) -> Pytree:
        """Weighted neighbor gather of decoded per-agent buffers, leaf-wise;
        pads (self index, weight 0) contribute exactly 0.  Accumulated one
        neighbor column at a time — deg_max cheap (n, d) row-gathers instead
        of one (n, deg, d) materialization, which is what makes the sparse
        path beat the dense matmul for n >= 32 (BENCH_gossip.json)."""
        nbr = jnp.asarray(self.neighbors)

        def one(x):
            w = jnp.asarray(self.weights, x.dtype)
            shape = (-1,) + (1,) * (x.ndim - 1)
            out = w[:, 0].reshape(shape) * x
            for j in range(nbr.shape[1]):
                out = out + w[:, 1 + j].reshape(shape) * x[nbr[:, j]]
            return out

        return tree_map(one, tree)

    def mix_encoded(self, payload: Pytree,
                    decode: Callable[[Pytree], Pytree]) -> Pytree:
        """W @ decode(payload) with one decode: decode commutes with the
        per-agent gather, so the single decoded copy serves every
        receiver."""
        return self.mix(decode(payload))

    def mix_masked(self, x: jnp.ndarray, mask: jnp.ndarray, *,
                   x_tx: jnp.ndarray = None,
                   cache: jnp.ndarray = None) -> jnp.ndarray:
        """Degraded sparse mix under a (n, deg_max) link-survival mask
        (core/faults.py; mask[i, j] = did neighbors[i, j] deliver to i).
        ``cache=None`` renormalizes the surviving table weights — dropped
        mass moves to the self column, rows stay stochastic, isolated
        rows degenerate to self-weight 1.0 (faults.renormalize_table); a
        cache buffer instead serves dropped links from the sender's last
        successful broadcast at full weight (stale policy).
        ``x_tx`` is the as-transmitted buffer (corruption applies to the
        wire copy); the self column always reads the clean local ``x``.
        Same O(n * deg * d) column-at-a-time accumulation as ``mix``;
        operates on one (n, ...) buffer, not a pytree."""
        from repro.core import faults as faults_mod
        nbr = jnp.asarray(self.neighbors)
        x_tx = x if x_tx is None else x_tx
        shape = (-1,) + (1,) * (x.ndim - 1)
        w = jnp.asarray(self.weights, x.dtype)
        if cache is None:
            wr = faults_mod.renormalize_table(w, mask).astype(x.dtype)
            out = wr[:, 0].reshape(shape) * x
            for j in range(nbr.shape[1]):
                out = out + wr[:, 1 + j].reshape(shape) * x_tx[nbr[:, j]]
            return out
        out = w[:, 0].reshape(shape) * x
        for j in range(nbr.shape[1]):
            src = nbr[:, j]
            val = jnp.where(mask[:, j].reshape(shape), x_tx[src], cache[src])
            out = out + w[:, 1 + j].reshape(shape) * val
        return out


@dataclasses.dataclass(frozen=True)
class HierarchicalGossip:
    """Two-level mixing for topology.hierarchical graphs (simulator path).

    Blocks of ``node_size`` consecutive agents form one node.  The intra
    level is exact dense averaging (``intra_mean`` — free, zero wire
    bits); only node-level buffers travel the compressed ``inter`` graph
    (an EncodedNeighborGossip over ``topo.inter``'s table).  For any
    buffer x,

        mix(x) = broadcast(W_inter @ intra_mean(x)) = kron(W_inter, J/s) @ x

    exactly — the composite dense mix, computed at node granularity
    (O(m * deg * d) instead of O(n^2 * d), m = n / s).  The engines'
    ``gossip="hier"`` path encodes each node's intra-mean ONCE and ships
    that single payload over the inter table, so wire accounting counts
    inter-node bytes only (payload / node_size per agent).

    ``node_view`` reads row 0 of each block — exact (not an estimate) for
    the block-constant buffers the hier engine path produces."""
    node_size: int
    inter: EncodedNeighborGossip

    @staticmethod
    def from_topology(topo) -> "HierarchicalGossip":
        """Backend for a topology.HierarchicalTopology."""
        return HierarchicalGossip(
            node_size=int(topo.node_size),
            inter=EncodedNeighborGossip.from_topology(topo.inter))

    @property
    def m(self):
        """Node count of the inter graph."""
        import numpy as np
        return int(np.asarray(self.inter.neighbors).shape[0])

    def intra_mean(self, x: jnp.ndarray) -> jnp.ndarray:
        """(n, ...) -> (m, ...) block means — the exact intra-node mix."""
        s = self.node_size
        return x.reshape((x.shape[0] // s, s) + x.shape[1:]).mean(axis=1)

    def node_view(self, x: jnp.ndarray) -> jnp.ndarray:
        """(n, ...) -> (m, ...) strided row-0-of-each-block view; equals
        ``intra_mean`` on block-constant buffers, with no flops."""
        return x[::self.node_size]

    def broadcast(self, xb: jnp.ndarray) -> jnp.ndarray:
        """(m, ...) node-level buffer -> (n, ...) block-constant buffer."""
        s = self.node_size
        m = xb.shape[0]
        rep = jnp.broadcast_to(xb[:, None], (m, s) + xb.shape[1:])
        return rep.reshape((m * s,) + xb.shape[1:])

    def mix(self, tree: Pytree) -> Pytree:
        """kron(W_inter, J/s) @ x leaf-wise (see class docstring)."""
        def one(x):
            return self.broadcast(self.inter.mix(self.intra_mean(x)))
        return tree_map(one, tree)


@dataclasses.dataclass(frozen=True)
class EncodedRingGossip:
    """Uniform-ring special case of EncodedNeighborGossip.  The engine
    substrate and the trainer now route through the Topology table / round
    decomposition instead; this class survives as the compact
    (w_self, w_neighbor) API for ring-only drivers and tests.

    ``mix_encoded`` decodes the payload ONCE and rolls the *decoded* buffer
    to the two ring neighbors (one for n == 2, none for n == 1): rolling
    commutes with per-agent decode, so this equals the old
    decode-at-every-receiver form bit for bit while skipping its two
    redundant decode passes (the ROADMAP's 3x-decode open item).
    """
    w_self: float = 1.0 / 3.0
    w_neighbor: float = 1.0 / 3.0

    @staticmethod
    def weights_from(W) -> "EncodedRingGossip":
        """Read (w_self, w_neighbor) off a uniform ring mixing matrix."""
        import numpy as np
        Wn = np.asarray(W)
        return EncodedRingGossip(w_self=float(Wn[0, 0]),
                                 w_neighbor=float(Wn[0, 1 % Wn.shape[0]]))

    def shift(self, tree: Pytree, direction: int) -> Pytree:
        """Roll every leaf by one agent along the ring."""
        return tree_map(lambda a: jnp.roll(a, -direction, axis=0), tree)

    def mix_encoded(self, payload: Pytree,
                    decode: Callable[[Pytree], Pytree]) -> Pytree:
        """w_self * own + w_neighbor * (right + left) on the decoded buffer
        (decoded once — see class docstring).

        Degenerate rings (topology.ring): n == 2 has ONE neighbor (both
        shifts would deliver the same agent — summing them double-counts),
        n == 1 has none."""
        n = jax.tree_util.tree_leaves(payload)[0].shape[0]
        own = decode(payload)
        if n == 1:
            return own
        right = self.shift(own, +1)
        if n == 2:
            return tree_map(
                lambda o, r: self.w_self * o + self.w_neighbor * r,
                own, right)
        left = self.shift(own, -1)
        return tree_map(
            lambda o, r, l: self.w_self * o + self.w_neighbor * (r + l),
            own, right, left)


def _ring_perms(n: int) -> Tuple[list, list]:
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    return fwd, bwd


@dataclasses.dataclass(frozen=True)
class RingGossip:
    """Ring mixing with uniform 1/3 weights via collective_permute.

    Retained as a public reference/compatibility helper: dist/trainer.py
    now schedules its collectives from ``Topology.permute_rounds()`` and no
    in-repo path calls this class — new code should go through a Topology
    (the fixed 1/3 weights here cover only the n >= 3 uniform ring).

    axes: mesh axis name(s) that form the agent ring (e.g. ("pod", "data")).
          jax.lax.ppermute accepts a tuple of axis names and flattens them in
          row-major order, so with ("pod", "data") the ring walks all agents
          of pod 0 then pod 1: exactly 2 inter-pod edges.
    """
    axes: Tuple[str, ...] = ("data",)
    w_self: float = 1.0 / 3.0
    w_neighbor: float = 1.0 / 3.0

    @property
    def axis_name(self):
        return self.axes if len(self.axes) > 1 else self.axes[0]

    def n_agents(self) -> jnp.ndarray:
        return jax.lax.axis_size(self.axis_name)

    def shift(self, tree: Pytree, direction: int) -> Pytree:
        """ppermute every leaf by +1/-1 around the ring (wire traffic!)."""
        n = jax.lax.axis_size(self.axis_name)
        fwd, bwd = _ring_perms(n)
        perm = fwd if direction > 0 else bwd

        def one(x):
            return jax.lax.ppermute(x, self.axis_name, perm)

        return tree_map(one, tree)

    def mix(self, tree: Pytree) -> Pytree:
        """w_self * x + w_nb * (left + right), leaf-wise, uncompressed."""
        right = self.shift(tree, +1)
        left = self.shift(tree, -1)

        def one(x, r, l):
            return self.w_self * x + self.w_neighbor * (r + l)

        return tree_map(one, tree, right, left)

    def mix_encoded(self, codes: Pytree, decode: Callable[[Pytree], Pytree]) -> Pytree:
        """W @ decode(codes) where only the *encoded* payload travels.

        `codes` is whatever the compressor's encode() produced (int8 code
        planes + per-block scales).  Each agent permutes the payload to its
        ring neighbors and decodes locally — this is the byte-accurate wire
        path whose collective traffic the roofline measures.
        """
        right = self.shift(codes, +1)
        left = self.shift(codes, -1)
        own = decode(codes)

        def one(o, r, l):
            return self.w_self * o + self.w_neighbor * (r + l)

        return tree_map(one, own, decode(right), decode(left))

    def i_minus_w(self, tree: Pytree) -> Pytree:
        mixed = self.mix(tree)
        return tree_map(jnp.subtract, tree, mixed)

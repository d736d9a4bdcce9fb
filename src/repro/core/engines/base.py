"""Shared substrate of the flat engine family.

Every flat engine — LEAD (engines/lead.py) and the paper's baselines
(engines/baselines.py) — keeps its per-agent state as contiguous
``(n_agents, nb, block)`` f32 buffers in the kernels' native block layout
(see kernels/__init__.py for the layout contract) and runs its iteration as
a handful of fused passes over those buffers.  This module holds everything
the family shares:

  * layout       — blockify/unblockify between the logical (n, d) view and
                   the padded (n, nb, block) buffers; zero rows are a fixed
                   point of every kernel, so the tile padding never leaks.
  * wire         — ``encode_payload``: the pre-communication stage.  The
                   compressor's flat wire protocol (``encode_blocks`` /
                   ``decode_blocks``, core/compression.py) turns the message
                   buffer into the *payload* — the only thing that may cross
                   agents — plus the byte-accurate per-agent bits it costs.
                   Identity/None short-circuits to a raw-values payload
                   (d * 32 bits), so the exact baselines ride the same path
                   with no encode stage.
  * gossip       — ``mix_payload``: pluggable communication stage over the
                   engine's ``Topology`` (core/topology.py).  The payload is
                   decoded ONCE (per-agent decode commutes with the
                   exchange); ``gossip="dense"`` then mixes W @ q densely,
                   ``gossip="neighbor"`` runs the sparse O(n * deg * d)
                   neighbor-exchange gather (EncodedNeighborGossip) — any
                   Assumption-1 graph, ring/torus/Erdős–Rényi alike.
                   ``gossip="ring"`` is the historical alias for neighbor
                   exchange that additionally asserts the topology IS the
                   uniform ring.  ``gossip="hier"`` (topology.hierarchical
                   graphs) runs the two-level wire: exact intra-node
                   averaging (free), ONE encode per node, neighbor
                   exchange over the inter graph only — wire bits are
                   inter-node bytes amortized per agent.  Independently,
                   ``topo.with_interval(tau)`` gates the whole wire at
                   ``k % tau == 0``; the other steps run the engine's
                   ``local_stage`` (zero bits, no gossip).
  * dither       — the quantizer dither plane.  ``dither="match"`` draws
                   per-agent threefry over the logical blocks, matching the
                   tree path's split-then-vmap draw bit for bit;
                   ``dither="fast"`` uses the counter-hash ``fast_uniform``
                   generator — statistically equivalent, much cheaper, a
                   different random stream.  For the paper's p=inf b-bit
                   quantizer, ``encode_payload`` feeds the plane straight
                   into the fused ``kernels.quantize.encode`` pass, so every
                   engine in the family (not just LEAD) gets the fused
                   kernel + fast-dither hot path.

Every engine's iteration is the same three-beat bar, and the base owns the
bar structure (``step_with_wire``):

    message(s, gb, hy)            -> (msg, ctx)      pre-communication math
    encode_payload / mix_payload                      the wire (base-owned)
    apply_stage(s, gb, q, wq, hy, ctx) -> (new, err)  post-communication math

``message`` and ``apply_stage`` are *pure elementwise algebra* over blocked
buffers — they carry the whole per-algorithm update and are deliberately
shape-polymorphic (any ``(n, nb, block)``), so the SAME methods drive both
the single-device flat path (the scan simulator) and the multi-host trainer
(dist/trainer.py), which blockifies each stacked pytree leaf, calls
``message``, ships the encoded payload through one shard_map ppermute per
``Topology.permute_rounds()`` entry, and calls ``apply_stage`` — one
implementation of every algorithm, two communication substrates.

Hyper-parameters are ``Schedule`` values (core/lead.py): floats OR callables
of the iteration counter k (Theorem 2 diminishing stepsizes).  The base
resolves them once per step via ``hypers_at(state.k)`` and hands the
stage methods a dict of step-k scalars, so schedules run *inside* the scan.

Engines driven directly by the scan simulator (core/simulator.py run())
implement the baseline driver protocol on top of this base:

    init(x0, g0, key)            -> state        (state.x blocked)
    step_with_wire(state, g, key) -> (new_state, comp_err, wire_bits)

with ``comp_err`` the *exact in-step* relative compression error of the
quantity the algorithm transmitted this iteration and ``wire_bits`` the
per-agent bits of the actual payload (data-dependent for RandK).  The base
derives ``step`` / ``step_with_metrics`` / ``x_of`` from that one method.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import faults as faults_mod
from repro.core import topology as topology_mod
from repro.core.gossip import (HIGHEST, DenseGossip, EncodedNeighborGossip,
                               HierarchicalGossip)
from repro.core.lead import _at
from repro.kernels import quantize as _q
from repro.kernels.ops import DEFAULT_BLOCK, _pick_tile

# _LAYOUT_FIELDS (defined right after FlatEngineBase below): the substrate's
# own dataclass fields — everything a subclass adds on top is an algorithm
# hyper-parameter (and may be a Schedule)


def _is_fused_quantizer(comp) -> bool:
    """True when the compressor is exactly what the fused Pallas kernels
    implement: the blockwise p=inf b-bit quantizer."""
    from repro.core.compression import QuantizePNorm
    return (isinstance(comp, QuantizePNorm)
            and comp.p in (jnp.inf, math.inf, "inf"))


def fast_uniform(shape, seed: jnp.ndarray) -> jnp.ndarray:
    """Counter-based U[0,1) dither: murmur3-style integer finalizer over an
    iota, keyed by a uint32 seed.  One hash per element (~5 int ops) versus
    ~dozens for threefry — the production dither of the flat engine's
    ``dither="fast"`` mode (the fused-kernel analogue of TPU's on-device
    pltpu.prng_random_bits path).  Quality is ample for quantization dither;
    it is NOT a cryptographic or jax.random-compatible stream."""
    m = 1
    for s in shape:
        m *= int(s)
    cnt = jax.lax.iota(jnp.uint32, m).reshape(shape)
    z = (cnt + seed.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)) \
        * jnp.uint32(0x85EBCA6B)
    z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
    z = z ^ (z >> 16)
    # top 24 bits -> [0, 1) with full f32 mantissa coverage
    return (z >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


@dataclasses.dataclass(frozen=True)
class FlatEngineBase:
    """Layout + wire + gossip substrate shared by every flat engine.

    topology is a core/topology.Topology (a raw mixing matrix is accepted
    and normalized in __post_init__): it carries the dense W for
    gossip="dense", the padded neighbor/weight table for
    gossip="neighbor", and the Theorem-1 spectral metadata.
    compressor=None (or Identity) means no encode stage: the raw message
    buffer is the payload (d * 32 bits on the wire).  `interpret` is the
    kernels' tri-state backend flag (None = auto).  The payload is decoded
    once per step; gossip="dense" mixes W @ q, gossip="neighbor" runs the
    sparse neighbor-exchange gather on any topology, and gossip="ring" is
    the alias that additionally asserts the topology is the uniform ring.
    dither selects the quantizer dither stream (see module docstring);
    "match" keeps trajectories aligned with the tree references, "fast" is
    the cheaper production stream.

    Subclasses add their hyper-parameter fields (eta/gamma/...), each a
    ``Schedule``: a float or a callable of the iteration counter k
    (Theorem 2).  They implement the two stage methods ``message`` and
    ``apply_stage`` plus the class metadata ``state_cls`` (the state
    NamedTuple) and ``consensus_init`` (how each non-x state field starts
    from a consensus point: "copy" of x0 or "zeros") — that metadata is what
    lets dist/trainer.py instantiate the same algorithm over stacked
    model pytrees without re-rolling its math.
    """
    topology: Any                      # Topology (or (n, n) matrix)
    dim: int                           # logical per-agent dimension d
    compressor: Any = None             # None -> Identity (no encode stage)
    block: int = DEFAULT_BLOCK
    interpret: Optional[bool] = None
    gossip: str = "dense"              # "dense" | "neighbor" | "ring" alias
    dither: str = "match"              # "match" | "fast"
    faults: Optional[Any] = None       # core/faults.FaultModel (None = clean)

    # subclass metadata: the state NamedTuple and its consensus start
    # (field -> "copy" of x0 | "zeros"); x and k are implicit
    state_cls: ClassVar[type] = None
    consensus_init: ClassVar[Dict[str, str]] = {}
    # declared wire fields: one name per buffer the algorithm transmits
    # each communication step.  Single-wire engines (everything before
    # C-GT) keep the default; a multi-wire engine (FlatCGTEngine ships an
    # iterate wire AND a tracker wire) overrides with one name per wire,
    # its ``message`` returns a same-length tuple of message buffers, and
    # ``apply_stage`` receives same-length tuples (q, wq).  The base's
    # encode/mix stages and dist/trainer.py loop over this declaration
    # instead of assuming one buffer.
    wire_fields: ClassVar[tuple] = ("msg",)

    def __post_init__(self):
        # materialize, not as_topology: a TopologyBank passes through, a
        # periodic schedule becomes a bank (the graph then varies inside
        # the scan), and a live (periodless) schedule is rejected loudly
        # instead of silently freezing at topo(0)
        object.__setattr__(self, "topology",
                           topology_mod.materialize(self.topology))
        assert self.gossip in ("dense", "neighbor", "ring", "hier"), \
            self.gossip
        assert self.dither in ("match", "fast"), self.dither
        assert self.faults is None or isinstance(self.faults,
                                                 faults_mod.FaultModel), \
            f"faults must be a core/faults.FaultModel, got {self.faults!r}"
        if self.faults is not None and self.n_wires > 1:
            assert self.faults.policy == "renormalize", \
                "multi-wire engines support only the 'renormalize' fault " \
                "policy: the stale cache holds ONE payload per agent but " \
                f"{type(self).__name__} ships {self.n_wires} wires per " \
                "exchange"
        assert not (self._bank and self.comm_interval > 1), \
            "comm_interval > 1 is not supported on a TopologyBank: " \
            "skipping rounds changes which round graph fires at which " \
            "step, and the round-indexed state recomputations (CHOCO/" \
            "LEAD bank branches) assume every round fires"
        if self.gossip == "hier":
            assert isinstance(self.topology,
                              topology_mod.HierarchicalTopology), \
                "gossip='hier' needs a topology.hierarchical(...) graph " \
                "(use gossip='neighbor' for flat topologies)"
            assert not self._hier or self.faults is None \
                or self.faults.policy == "renormalize", \
                "hier gossip supports only the 'renormalize' fault " \
                "policy: the stale cache is agent-granular but the hier " \
                "wire is node-granular"
        if self.gossip == "ring":
            import numpy as np
            assert not self._bank, \
                "gossip='ring' is the static uniform-ring alias and does " \
                "not support TopologyBank (use gossip='neighbor')"
            W = self.topology.W
            assert np.allclose(W, np.asarray(topology_mod.ring(W.shape[0])),
                               atol=1e-6), \
                "gossip='ring' requires the uniform ring mixing matrix " \
                "(use gossip='neighbor' for arbitrary topologies)"

    @property
    def _bank(self) -> bool:
        """True when the engine mixes over a round-indexed TopologyBank
        (time-varying gossip carried through the scan)."""
        return isinstance(self.topology, topology_mod.TopologyBank)

    @property
    def n_wires(self) -> int:
        """Number of buffers this engine ships per communication step."""
        return len(self.wire_fields)

    @property
    def comm_interval(self) -> int:
        """tau: the topology's communication interval (1 = every step)."""
        return int(getattr(self.topology, "comm_interval", 1))

    @property
    def node_size(self) -> int:
        """Agents per node of a hierarchical topology (1 otherwise)."""
        return int(getattr(self.topology, "node_size", 1))

    @property
    def _hier(self) -> bool:
        """True when the engine runs the two-level wire: exact intra-node
        averaging (free) + encoded inter-node exchange.  node_size == 1
        deliberately stays False — the composite graph then IS the inter
        graph and the existing neighbor-gather path runs bit-identically."""
        return self.gossip == "hier" and self.node_size > 1

    def _hg(self) -> HierarchicalGossip:
        return HierarchicalGossip.from_topology(self.topology)

    @property
    def W(self):
        """The dense (n, n) mixing matrix of the engine's topology."""
        return self.topology.W

    @property
    def n(self) -> int:
        return self.topology.n

    @property
    def nb_logical(self) -> int:
        """Blocks the tree-path compressor sees: ceil(d / block)."""
        return -(-self.dim // self.block)

    @property
    def tile_b(self) -> int:
        return _pick_tile(self.dim, self.block, _q.DEFAULT_TILE_B)

    @property
    def nb(self) -> int:
        """nb_logical rounded up to a tile multiple (bounded padding; the
        kernels themselves take any row count)."""
        return -(-self.nb_logical // self.tile_b) * self.tile_b

    # -- layout ------------------------------------------------------------
    def blockify(self, arr: jnp.ndarray) -> jnp.ndarray:
        """(n, d) -> (n, nb, block), zero-padded past d."""
        n = arr.shape[0]
        pad = self.nb * self.block - self.dim
        flat = jnp.pad(arr.astype(jnp.float32), ((0, 0), (0, pad)))
        return flat.reshape(n, self.nb, self.block)

    def unblockify(self, buf: jnp.ndarray) -> jnp.ndarray:
        """(n, nb, block) -> (n, d)."""
        return buf.reshape(buf.shape[0], -1)[:, :self.dim]

    def _blockify_g(self, g: jnp.ndarray) -> jnp.ndarray:
        """Gradients arrive either (n, d) or already in the native
        (n, nb, block) layout, which skips the per-step padding copy."""
        return g if g.ndim == 3 else self.blockify(g)

    def _mix(self, buf: jnp.ndarray, k=None) -> jnp.ndarray:
        """W @ buf along the agent axis (pads are zero -> stay zero).
        Flattened to one 2-D matmul so the lowering matches the tree path's
        (n, d) mix exactly.  With a TopologyBank and a (traced) step index
        k, the step's round matrix is sliced from the stacked bank; k=None
        keeps the init-time convention (round 0 — at a consensus start
        every round fixes the iterate, so the choice is immaterial)."""
        if self._bank and k is not None:
            r = jnp.asarray(k, jnp.int32) % self.topology.period
            W = jnp.asarray(self.topology.Ws, buf.dtype)[r]
        else:
            W = jnp.asarray(self.W, buf.dtype)
        return jnp.matmul(W, buf.reshape(buf.shape[0], -1),
                          precision=HIGHEST).reshape(buf.shape)

    def mix_round(self, buf: jnp.ndarray, k) -> jnp.ndarray:
        """W_k @ buf through the engine's gossip backend: the step's round
        graph on a bank (traced slice), the fixed W otherwise.  For engine
        state that is NOT wire traffic (reference buffers like LEAD's H,
        which receivers track as replicas in a real deployment), so the
        fault layer's link masks never apply here."""
        if not self._bank:
            return self._mix(buf)
        if self.gossip == "dense":
            return DenseGossip.for_round(self.topology, k).mix(buf)
        return EncodedNeighborGossip.for_round(self.topology, k).mix(buf)

    def _rows(self, buf: jnp.ndarray) -> jnp.ndarray:
        """(n, nb, block) -> (n*nb, block): one kernel call for all agents.
        Shape-derived (not read off the engine's dim) so the same kernels run
        on the trainer's per-leaf buffers, whose nb differs per leaf."""
        return buf.reshape(-1, buf.shape[-1])

    # -- hyper-parameters ----------------------------------------------------
    @property
    def hyper_fields(self):
        """Names of this engine's algorithm hypers (dataclass fields beyond
        the layout substrate), each a Schedule (float or callable of k)."""
        return tuple(f.name for f in dataclasses.fields(self)
                     if f.name not in _LAYOUT_FIELDS)

    def hypers_at(self, k) -> Dict[str, jnp.ndarray]:
        """Resolve every hyper Schedule at iteration k (f32 scalars)."""
        return {f: _at(getattr(self, f), k) for f in self.hyper_fields}

    # -- dither ------------------------------------------------------------
    def _dither_plane(self, key: jax.Array, k: jnp.ndarray,
                      n_rows: Optional[int] = None) -> jnp.ndarray:
        """U[0,1) dither (n_rows, nb, block) for the fused quantizer path
        (n_rows defaults to the agent count; the hier wire draws node-level
        planes instead).  "match": per-row threefry over the logical
        blocks, matching the tree path's split-then-vmap draw bit for bit
        (tile padding rows get zeros — codes there are zero regardless of
        dither).  "fast": one counter-hash pass seeded from (key, iteration
        counter k)."""
        rows = self.n if n_rows is None else n_rows
        if self.dither == "fast":
            raw = (key if jnp.issubdtype(key.dtype, jnp.integer)
                   else jax.random.key_data(key))
            seed = jnp.bitwise_xor(jnp.ravel(raw)[-1].astype(jnp.uint32),
                                   k.astype(jnp.uint32))
            return fast_uniform((rows, self.nb, self.block), seed)
        keys = jax.random.split(key, rows)
        shape = (self.nb_logical, self.block)
        u = jax.vmap(lambda kk: jax.random.uniform(kk, shape, jnp.float32))(keys)
        return jnp.pad(u, ((0, 0), (0, self.nb - self.nb_logical), (0, 0)))

    # -- wire --------------------------------------------------------------
    def encode_payload(self, key: jax.Array, buf: jnp.ndarray, k=None):
        """Pre-communication stage: (payload, decode, wire_bits) for the
        message `buf` (n, nb, block).

        payload is everything that may cross agents; decode maps it back to
        the (n, nb, block) estimate; wire_bits is the per-agent bits of the
        actual payload.  Identity/None ships the raw buffer (d * 32 bits).
        The paper's p=inf quantizer takes the fused kernels.quantize.encode
        pass fed by the engine's dither plane (`k` seeds dither="fast");
        every other operator goes through its encode_blocks wire path."""
        comp = self.compressor
        from repro.core.compression import Identity
        if comp is None or isinstance(comp, Identity):
            bits = jnp.asarray(self.dim * 32, jnp.float32)
            return {"values": buf}, (lambda pl: pl["values"]), bits
        if not hasattr(comp, "encode_blocks"):
            raise NotImplementedError(
                f"{type(comp).__name__} does not implement the flat "
                "encode_blocks/decode_blocks wire protocol")
        if _is_fused_quantizer(comp):
            kk = jnp.zeros((), jnp.int32) if k is None else k
            u = self._dither_plane(key, kk, n_rows=buf.shape[0])
            code, scale = _q.encode(self._rows(buf), self._rows(u),
                                    bits=comp.bits, interpret=self.interpret)
            return self.quant_payload(code, scale, comp.bits)
        payload, bits = comp.encode_blocks(key, buf, self.dim,
                                           interpret=self.interpret)
        return payload, comp.decode_blocks, bits

    def quant_payload(self, code: jnp.ndarray, scale: jnp.ndarray,
                      bits: int):
        """(payload, decode, wire_bits) for fused-quantizer outputs: code
        int8 / scale f32 in row layout (n*nb, ...).  Single source of truth
        for the quantizer's payload shape, receiver decode, and wire-bit
        accounting across the family (LEAD's lead_diff_encode and the
        base's quantize.encode both land here).  The row count is derived
        from the code (-1), not read off the engine — the hier wire runs
        this on node-level (m * nb, block) buffers."""
        shape3 = (-1, self.nb, self.block)
        payload = {"code": code.reshape(shape3),
                   "scale": scale.reshape(-1, self.nb, 1)}

        def decode(pl):
            rows = _q.decode(pl["code"].reshape(-1, self.block),
                             pl["scale"].reshape(-1, 1), bits=bits,
                             interpret=self.interpret)
            return rows.reshape(shape3)

        wire = jnp.asarray(self.dim * (bits + 1) + self.nb_logical * 32,
                           jnp.float32)
        return payload, decode, wire

    def mix_payload(self, payload, decode, k=None):
        """Communication stage: (q, W q) with q = decode(payload), decoded
        exactly ONCE (per-agent decode commutes with the exchange, so the
        single decoded copy serves the receiver-own view and the mix).
        Only `payload` conceptually crosses agents; gossip="dense" mixes
        densely, "neighbor"/"ring" run the sparse neighbor-exchange gather
        over the topology's padded table.

        With a TopologyBank the (traced) step index ``k`` selects the
        round graph ``k % P`` — the backends' ``for_round`` slices the
        stacked matrices/tables inside the trace, so the graph varies
        per iteration of ONE compiled scan.  The static path is untouched
        (bit-identical to the pre-bank substrate).

        The optimization_barrier pins the decode-once property at the XLA
        level: the gather's per-neighbor consumers would otherwise inline
        the decode as a fusion producer and recompute it per neighbor —
        the 3x-decode cost this path exists to avoid (and the same
        materialize-once discipline the trainer's shard_map needs for
        knife-edge floor() consistency, ARCHITECTURE.md §3).

        Multi-wire engines hand a tuple of payloads with a same-length
        tuple of decodes (one per declared wire field); the stage loops
        the wires through one exchange each and returns tuple-valued
        (q, wq)."""
        if isinstance(decode, tuple):
            outs = [self.mix_payload(pl, dec, k=k)
                    for pl, dec in zip(payload, decode)]
            return tuple(o[0] for o in outs), tuple(o[1] for o in outs)
        q = decode(payload)
        if self._hier:
            # two-level wire: q is block-constant (the hier decode
            # broadcasts each node's single payload), so its node view is
            # exact; only node-level buffers travel the inter graph —
            # O(m * deg * d) mixing, inter-node bytes only
            hg = self._hg()
            q = jax.lax.optimization_barrier(q)
            return q, hg.broadcast(hg.inter.mix(hg.node_view(q)))
        if self._bank:
            kk = jnp.zeros((), jnp.int32) if k is None else k
            if self.gossip == "dense":
                return q, DenseGossip.for_round(self.topology, kk).mix(q)
            q = jax.lax.optimization_barrier(q)
            return q, EncodedNeighborGossip.for_round(self.topology,
                                                      kk).mix(q)
        if self.gossip == "dense":
            return q, self._mix(q)
        q = jax.lax.optimization_barrier(q)
        return q, EncodedNeighborGossip.from_topology(self.topology).mix(q)

    # -- fault injection + graceful degradation ------------------------------
    def init_fault_state(self, state) -> faults_mod.FaultState:
        """Fresh FaultState (stale cache + staleness ages) for a run of
        this engine — carried alongside the engine state through the scan
        by drivers on the faulted path (core/simulator.py run())."""
        assert self.faults is not None, "engine has no FaultModel attached"
        return faults_mod.init_fault_state(self.faults, state.x)

    def mix_payload_faulted(self, payload, decode, k, fstate):
        """The communication stage under the engine's FaultModel: returns
        ``(q, wq, new_fstate)`` where q is the clean own decode (an agent
        needs no wire to read its own payload) and wq the *degraded* mix —
        links that did not deliver at step k are either renormalized away
        (policy="renormalize": the realized mixing matrix stays
        row-stochastic, so the consensus contraction survives with a
        weaker step graph) or served from the stale cache of the sender's
        last successful broadcast (policy="stale").  Undetected bit-flip
        corruption is applied to the wire copy only, never to q or the
        self column.  The fault realization is the counter hash of
        (seed, k, edge) — deterministic and replayable (core/faults.py).

        Multi-wire engines (tuple payload/decode) exchange every wire over
        the SAME physical round: the link realization is the counter hash
        of (seed, k, edge), so each per-wire pass derives the identical
        mask — a dropped link loses every wire of the exchange at once, as
        one lost packet would.  The FaultState advances once (the per-wire
        age updates are identical; policy='renormalize' is asserted at
        construction, so there is no per-wire cache to disambiguate)."""
        fm = self.faults
        topo = self.topology
        if isinstance(decode, tuple):
            qs, wqs, fs = [], [], fstate
            for pl, dec in zip(payload, decode):
                q_j, wq_j, fs = self.mix_payload_faulted(pl, dec, k, fstate)
                qs.append(q_j)
                wqs.append(wq_j)
            return tuple(qs), tuple(wqs), fs
        q = decode(payload)
        if self._hier:
            # faults are realized at the wire's granularity: node -> node
            # inter links and node broadcasts (the intra level is exact
            # local arithmetic — nothing to drop).  An inter-link loss
            # stalls every agent of the receiving node equally, so the
            # staleness age repeats node-wise over agents.
            hg = self._hg()
            s = self.node_size
            # decode-once: same barrier discipline as the clean path
            q = jax.lax.optimization_barrier(q)
            qn = hg.node_view(q)
            qn_tx = fm.corrupt_values(qn, k)
            mask = fm.table_mask(k, hg.inter.neighbors)
            wq = hg.broadcast(hg.inter.mix_masked(qn, mask, x_tx=qn_tx))
            ok = jnp.repeat(fm.broadcast_ok(k, hg.m), s)
            age = jnp.where(ok, 0, fstate.age + 1)
            return q, wq, faults_mod.FaultState(cache=fstate.cache, age=age)
        q_tx = fm.corrupt_values(q, k)
        cache = fstate.cache if fm.policy == "stale" else None
        if self.gossip == "dense":
            mask = fm.dense_mask(k, self.n)
            gb_dense = (DenseGossip.for_round(topo, k) if self._bank
                        else DenseGossip(W=topo))
            wq = gb_dense.mix_masked(q, mask, x_tx=q_tx, cache=cache)
        else:
            # the link mask composes with the *step's* graph: for a bank
            # the survival is evaluated over the round-(k % P) neighbor
            # table (a traced slice), so only links that exist this round
            # are dropped/renormalized
            gb_nbr = (EncodedNeighborGossip.for_round(topo, k) if self._bank
                      else EncodedNeighborGossip.from_topology(topo))
            mask = fm.table_mask(k, gb_nbr.neighbors)
            # decode-once: same barrier discipline as the clean path
            q, q_tx = jax.lax.optimization_barrier((q, q_tx))
            wq = gb_nbr.mix_masked(q, mask, x_tx=q_tx, cache=cache)
        ok = fm.broadcast_ok(k, self.n)
        age = jnp.where(ok, 0, fstate.age + 1)
        new_cache = fstate.cache
        if fm.policy == "stale":
            sel = ok.reshape((self.n,) + (1,) * (q.ndim - 1))
            new_cache = jnp.where(sel, q_tx, fstate.cache)
        return q, wq, faults_mod.FaultState(cache=new_cache, age=age)

    @staticmethod
    def rel_err(q: jnp.ndarray, target: jnp.ndarray,
                ref: jnp.ndarray) -> jnp.ndarray:
        """Exact in-step compression error of the transmitted message under
        the Trace convention — delegates to the single-source
        core.compression.rel_err (shared with the tree baselines)."""
        from repro.core.compression import rel_err
        return rel_err(q, target, ref)

    # -- the algorithm stage protocol ---------------------------------------
    def message(self, s, gb, hy):
        """Pre-communication math: (msg, ctx).  `msg` is the buffer the
        algorithm transmits this step (what gets encoded); `ctx` is whatever
        apply_stage needs back (e.g. the pre-communication iterate for the
        comp_err denominator).  Pure elementwise algebra — shape-polymorphic
        over any (n, nb, block) buffers."""
        raise NotImplementedError

    def apply_stage(self, s, gb, q, wq, hy, ctx):
        """Post-communication math: (new_state, comp_err) given the decoded
        own message q and its gossip mix wq.  Same polymorphism contract as
        `message` — dist/trainer.py calls both on per-leaf buffers."""
        raise NotImplementedError

    def encode_stage(self, s, gb, key, hy):
        """message + wire encode: (payload, decode, wire_bits, ctx).
        Engines with a fused message+encode kernel (LEAD's lead_diff_encode)
        override this; everyone else composes the two stages.

        On the hier wire the message is intra-node averaged FIRST (exact,
        free) and each node encodes its mean ONCE — the payload has m =
        n / node_size rows, the decode broadcasts the node estimate back to
        its agents (block-constant q), and the per-agent wire bits are the
        node payload amortized over its agents (inter-node bytes only).

        Multi-wire engines return a tuple of messages; each wire j encodes
        under the sub-key fold_in(key, j) (its tree twin draws the same
        stream), and the stage returns tuple payloads/decodes with the
        per-agent bits SUMMED over wires — both buffers really cross the
        wire every exchange."""
        msg, ctx = self.message(s, gb, hy)
        if self.n_wires > 1:
            assert isinstance(msg, tuple) and len(msg) == self.n_wires, \
                (type(self).__name__, self.wire_fields)
            payloads, decodes = [], []
            bits_total = jnp.zeros((), jnp.float32)
            for j, m in enumerate(msg):
                pl, dec, bits, _ = self._encode_one(
                    jax.random.fold_in(key, j), m, s.k)
                payloads.append(pl)
                decodes.append(dec)
                bits_total = bits_total + bits
            return tuple(payloads), tuple(decodes), bits_total, ctx
        payload, decode, bits, _ = self._encode_one(key, msg, s.k)
        return payload, decode, bits, ctx

    def _encode_one(self, key, msg, k):
        """One wire's encode (hier-aware): (payload, decode, bits, None)."""
        if self._hier:
            hg = self._hg()
            payload, node_decode, bits = self.encode_payload(
                key, hg.intra_mean(msg), k=k)
            return (payload, lambda pl: hg.broadcast(node_decode(pl)),
                    bits / self.node_size, None)
        payload, decode, bits = self.encode_payload(key, msg, k=k)
        return payload, decode, bits, None

    def local_stage(self, s, gb, hy):
        """The non-communication step of the tau-interval path
        (``k % comm_interval != 0``): (new_state, comp_err) with ZERO wire
        traffic.  Default: self-delivery — the message is its own q and wq
        (the W = I step), which is exactly right for engines that transmit
        (a surrogate of) their iterate and mix it in (DGD, NIDS, EXTRA,
        D2, QDGD, DeepSqueeze): the gossip term cancels and the gradient
        part of the update runs.  Engines whose apply_stage advances a
        *communication tracking state* (LEAD's h/hw/d, CHOCO's xhat, DCD's
        hats) override this to freeze that state instead — self-delivery
        would silently corrupt their tracking invariants."""
        msg, ctx = self.message(s, gb, hy)
        return self.apply_stage(s, gb, msg, msg, hy, ctx)

    def _intra_project(self, state):
        """Block-average every agent-leading state buffer of a hier engine
        (exact intra-node averaging — local arithmetic, zero wire).  Run
        after apply_stage on communication steps: it makes each node one
        logical agent of the inter-graph algorithm seeing its block-mean
        gradient, which is the invariant the hier convergence argument
        (and LEAD's hw = W h tracking) rests on.  Scalar fields (k) pass
        through."""
        hg = self._hg()

        def avg(v):
            if getattr(v, "ndim", 0) >= 1 and v.shape[0] == self.n:
                return hg.broadcast(hg.intra_mean(v))
            return v

        return jax.tree_util.tree_map(avg, state)

    def _step_core(self, s, g, key, hy):
        """The family's one iteration shape: encode -> gossip -> apply.
        With ``comm_interval`` tau > 1 the whole wire (encode + gossip +
        apply) fires only at ``k % tau == 0`` behind a lax.cond; the other
        steps run ``local_stage`` (zero bits, comp_err 0).  tau == 1 takes
        the branch-free path — its jaxpr is exactly the pre-interval
        substrate's."""
        gb = self._blockify_g(g)

        def comm(_):
            payload, decode, bits, ctx = self.encode_stage(s, gb, key, hy)
            q, wq = self.mix_payload(payload, decode, k=s.k)
            new, comp_err = self.apply_stage(s, gb, q, wq, hy, ctx)
            if self._hier:
                new = self._intra_project(new)
            return new, comp_err, bits

        tau = self.comm_interval
        if tau == 1:
            return comm(None)

        def local(_):
            new, _ = self.local_stage(s, gb, hy)
            zero = jnp.zeros((), jnp.float32)
            return new, zero, zero

        return jax.lax.cond(s.k % tau == 0, comm, local, None)

    # -- baseline driver protocol (engines driven directly by run()) --------
    def step_with_wire(self, state, g, key):
        """(new_state, comp_err, wire_bits) with the engine's stored hypers
        resolved at state.k (schedules supported)."""
        return self._step_core(state, g, key, self.hypers_at(state.k))

    def step_with_wire_faulted(self, state, fstate, g, key):
        """Faulted twin of step_with_wire: same iteration shape, but the
        communication stage goes through mix_payload_faulted and a
        FaultState rides along.  Returns (new_state, new_fstate, comp_err,
        wire_bits).  Engines that override encode_stage/apply_stage (LEAD's
        fused kernel included) inherit this unchanged.  Non-communication
        steps of a tau-interval run leave the FaultState untouched — no
        wire fired, so nothing could drop and staleness ages do not
        advance."""
        hy = self.hypers_at(state.k)
        gb = self._blockify_g(g)

        def comm(_):
            payload, decode, bits, ctx = self.encode_stage(state, gb, key,
                                                           hy)
            q, wq, fs = self.mix_payload_faulted(payload, decode, state.k,
                                                 fstate)
            new, comp_err = self.apply_stage(state, gb, q, wq, hy, ctx)
            if self._hier:
                new = self._intra_project(new)
            return new, fs, comp_err, bits

        tau = self.comm_interval
        if tau == 1:
            return comm(None)

        def local(_):
            new, _ = self.local_stage(state, gb, hy)
            zero = jnp.zeros((), jnp.float32)
            return new, fstate, zero, zero

        return jax.lax.cond(state.k % tau == 0, comm, local, None)

    def x_of(self, state):
        """Current iterates as (n, d) regardless of the blocked layout."""
        return self.unblockify(state.x)

    def step_with_metrics(self, state, g, key):
        new, comp_err, _ = self.step_with_wire(state, g, key)
        return new, comp_err

    def step(self, state, g, key):
        return self.step_with_wire(state, g, key)[0]


# derived, not hand-maintained: a field added to the base is automatically a
# layout knob, never a hyper (hyper_fields / hypers_at and the dist
# trainer's hyper validation all subtract this set)
_LAYOUT_FIELDS = tuple(f.name for f in dataclasses.fields(FlatEngineBase))

"""Decentralized multi-device trainer: the engine family over stacked model
pytrees, with codes on the wire.

``DistConfig.algorithm`` resolves through the same ``engine_for`` registry
as the single-device simulator (core/engines): LEAD and every paper
baseline — CHOCO-SGD, DeepSqueeze, QDGD, DCD-SGD compressed; DGD, NIDS,
EXTRA, D2 exact — run multi-host from one implementation of their update
math.  The trainer holds NO per-algorithm algebra of its own: each step it
hands every stacked train-state leaf to the kernels as ``(A, rows, C)``
rows of its own last dim C (a bitcast of the leaf; see _leaf_view) or,
where C is not a multiple of the block, as the flattened leaf padded to
``(A, nb, block)``, calls the engine's ``message`` stage, ships the encoded
payload through the ring, and calls the engine's ``apply_stage``
(engines/base.py documents the stage protocol).  ``allreduce`` is the one
special case — it is not a decentralized algorithm but the centralized
SGD reference (x -= eta * pmean(g)), kept for A/B comparisons.

Layout: every train-state leaf is *stacked* — leading axis A = number of
agents, sharded over the profile's agent mesh axes (one agent per device
slice; see dist/sharding.py).  The engine state fields beyond the iterate
(H/H_w/D for LEAD, xhat/xhat_w for CHOCO/DCD, ...) live in
``TrainState.algo`` as pytrees shaped like the params, created from the
engine's ``consensus_init`` spec — at a consensus start W x = x, so no init
communication is needed.  Gradients come from a vmapped AD pass over the
stacked params (GSPMD parallelizes it along the agent axis); the
inter-agent communication is a fully-manual shard_map over ALL mesh axes
whose ``jax.lax.ppermute`` schedule is derived from the run's
``core/topology.Topology`` (``DistConfig.topology``: ring by default,
torus_2d / erdos_renyi / any Assumption-1 graph): each
``Topology.permute_rounds()`` entry is one partial permutation of the
flattened agent axes, exchanged and decoded at the receiver — the only
collectives of an iteration, and the reason the lowering contains
collective-permute ops.  A ``TopologyBank`` (time-varying gossip:
exp-onepeer, random-matching, any periodic schedule) compiles each round
graph's permute schedule into one step and selects the step's graph with
``lax.switch(step % P)`` inside the shard_map — deg-1 one-peer rounds ship
exactly ONE ppermute per step, so per-step wire traffic is proportional
to the round degree, not the union graph's.

Two-level and interval gossip ride on the Topology object.  A
``topology.hierarchical(inter, node_size)`` graph maps its node blocks
onto the TRAILING agent mesh axes: messages take an exact ``lax.pmean``
over the intra-node axes (jnp.mean-class intra-node traffic, zero wire
bits), and only the lane-wise inter graph ``kron(W_inter, I_s)`` is
decomposed into ppermute rounds — on the block-constant payloads the
intra mean produces, lane-wise mixing equals the composite
``kron(W_inter, J_s/s)`` exactly, and after apply the full engine state
is projected back to block-constant (each node is one logical agent).
``Topology.with_interval(tau)`` gates the entire comm stage on
``step % tau``: skipped steps run the engine's ``local_stage`` — no
encode, no collective, zero reported wire bits — and faulted runs
realize link drops only on the rounds that actually fire.

Codes on the wire: compressed algorithms encode each leaf's message with
the Compressor flat protocol (``encode_blocks`` / ``decode_blocks``,
core/compression.py) *before* the gossip shard_map — the quantizer in a
shard_map of its own, on each device's own agents (``encode_agents``), as
XLA cannot partition its Pallas kernel; inside the gossip shard_map only
the payload (int8 code planes + per-block f32 scales for the quantizer;
kept values for RandK/TopK) crosses agents — each gossip round's ppermute
output is decoded at the receiver.  Exact algorithms ship the raw f32 leaf
(d * 32 bits).  With ``wire_pack=True`` quantizer codes additionally travel
as dense uint32 words (kernels.ops.pack_codes) — the byte-accurate ICI
payload.  Each step's metrics include ``bits_per_agent``, the actual
payload bits summed over leaves — the same accounting as
Trace.bits_per_agent in the simulator.

Hyper-parameters (``DistConfig.hyper``) are Schedule values — floats or
callables of the step counter (Theorem 2 diminishing stepsizes) — resolved
by the engine at ``state.step`` inside the jitted step.

Beyond-paper knobs: ``seq_parallel`` shards the residual stream's sequence
dim over the tp axis (the model's _seq_shard constraint), ``microbatches``
re-schedules the gradient pass as an accumulating scan, ``compute_dtype`` /
``state_dtype`` select bf16 compute/state.

Invariants mirror core/lead.py: 1^T D = 0 to roundoff for any compression
error (tests/dist_worker.py asserts it after 20 distributed steps), and the
permute-round mixing equals the dense ``topology.W`` matrix multiply for
every graph (dist_worker's registry_equivalence pins LEAD and NIDS against
hand-rolled dense-W references step for step; topology_multihost pins NIDS
on torus_2d and erdos_renyi the same way).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import faults as faults_mod
from repro.core import topology
from repro.core.compression import QuantizePNorm
from repro.core.engines import ENGINES, engine_for, is_exact
from repro.core.engines.base import _LAYOUT_FIELDS
from repro.core.lead import LEADHyper, _at
from repro.dist import sharding as shr
from repro.kernels.ops import pack_codes, unpack_codes
from repro.kernels.quantize import MAX_WIDTH
from repro.models import transformer as tfm
from repro.optim.optimizers import SGD
from repro.utils.finite import assert_finite_tree, finite_checks_enabled
from repro.utils.tree import tree_map, tree_zeros_like

Pytree = Any


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Distributed-run configuration (algorithm + wire + schedule knobs).

    algorithm is any core/engines registry key (lead, choco, deepsqueeze,
    qdgd, dcd, dgd, nids, extra, d2 + aliases) or "allreduce".  compressor
    overrides the wire operator; None picks the paper default — the
    blockwise p=inf quantizer QuantizePNorm(bits, block) for compressed
    algorithms, nothing for exact ones.

    topology selects the communication graph the agents gossip over: None
    -> the paper's uniform ring; a core/topology builder name ("ring",
    "torus", "erdos_renyi", "chain", "star", "full", or a time-varying
    family like "exp-onepeer" / "random-matching"); a Topology or
    TopologyBank instance (n must equal the mesh's agent count); a list of
    round graphs (validated into a bank); or a callable n_agents ->
    Topology | TopologyBank.  The trainer derives one shard_map
    collective-permute schedule per round graph from
    Topology.permute_rounds() — no ring assumption — and on a bank selects
    the step's schedule with lax.switch(step % P) inside the shard_map.
    Periodic schedules (with_schedule(fn, period=P)) materialize into
    banks; live periodless schedule callables raise (the compiled step
    cannot trace them and would silently freeze the graph at topo(0)).
    A topology.hierarchical(inter, node_size) graph runs two-level
    gossip (node_size must be the product of trailing agent mesh axes),
    and Topology.with_interval(tau) makes the step gossip only every
    tau-th iteration — see the module docstring.

    hyper sets the algorithm hyper-parameters; every value is a Schedule
    (float or callable of the step counter).  Three forms:
      * None (default) — the engine's own paper defaults, with the primal
        stepsize eta = 0.03 (the trainer's LM-tuned default);
      * a dict of exactly the hypers the engine declares (e.g.
        {"eta": 0.03, "gamma": 0.3} for CHOCO; NIDS declares eta only) —
        unknown keys raise, nothing is silently dropped;
      * a LEADHyper (eta/gamma/alpha) for LEAD and allreduce; passing one
        to an engine that does not declare all three raises, pointing at
        the dict form.

    interpret is the kernels' tri-state backend flag (None = auto: jnp on
    CPU, Pallas on TPU).

    faults attaches a core/faults.FaultModel: the shard_map comm stage then
    masks each gossip round with the model's deterministic link_ok
    realization (keyed on state.step — the fault schedule replays
    identically across restarts and checkpoint-resumes) and degrades by
    the mass-to-self renormalization.  The trainer supports
    policy="renormalize" with detect_corruption=True; the stale policy and
    undetected bit flips are single-device simulator modes.
    """
    algorithm: str = "lead"
    bits: int = 2                        # default quantizer bit-width
    block: int = 512                     # quantization block (paper: 512)
    compressor: Any = None               # explicit Compressor override
    topology: Any = None                 # None -> ring | name | Topology |
                                         # callable n_agents -> Topology
    hyper: Any = None                    # None | dict | LEADHyper (see above)
    optimizer: Any = SGD()
    seq_parallel: bool = False           # shard seq dim over tp between blocks
    wire_pack: bool = False              # ship codes as packed uint32 words
    microbatches: int = 1                # grad accumulation over batch chunks
    compute_dtype: str = "float32"
    state_dtype: str = "float32"
    interpret: Optional[bool] = None     # kernel backend (None = auto)
    faults: Any = None                   # core/faults.FaultModel (see below)

    def __post_init__(self):
        if self.algorithm != "allreduce":
            key = self.algorithm.lower().replace("_", "-")
            assert key in ENGINES, (
                f"unknown algorithm {self.algorithm!r}; registry has "
                f"{sorted(set(ENGINES))} + 'allreduce'")
        if self.faults is not None:
            assert isinstance(self.faults, faults_mod.FaultModel), self.faults
            if self.faults.is_active:
                assert self.algorithm != "allreduce", (
                    "fault injection degrades the decentralized gossip "
                    "stage; the centralized allreduce reference has none")
                assert self.faults.policy == "renormalize", (
                    "the multi-host trainer supports policy='renormalize' "
                    "only (the stale policy needs a per-leaf payload cache "
                    "— use the single-device simulator for it)")
                assert self.faults.detect_corruption, (
                    "undetected bit-flip corruption is a single-device "
                    "simulator mode; the trainer models detected "
                    "corruption as sender-side link drops")


_DEFAULT_ETA = 0.03                      # the trainer's LM-tuned stepsize


def _hyper_dict(dc: DistConfig) -> Dict[str, Any]:
    """DistConfig.hyper normalized to a plain {name: Schedule} dict (see
    the DistConfig docstring for the three accepted forms)."""
    h = dc.hyper
    if h is None:
        return {"eta": _DEFAULT_ETA}
    if isinstance(h, LEADHyper):
        return {f: getattr(h, f) for f in ("eta", "gamma", "alpha")}
    return dict(h)


def topology_of(dc: DistConfig, n_agents: int):
    """Resolve DistConfig.topology for an n_agents mesh (see the DistConfig
    docstring for the accepted forms) to a Topology or TopologyBank.

    Everything funnels through core/topology.materialize: a TopologyBank
    or list of rounds passes through bank validation, a periodic schedule
    (``with_schedule(fn, period=P)``) expands into the bank of its P
    rounds, and a live (periodless) schedule raises — the trainer compiles
    ONE gossip schedule into the step, so a callable it cannot enumerate
    would silently freeze at topo(0)."""
    t = dc.topology
    if t is None:
        return topology.ring(n_agents)
    if isinstance(t, str):
        topo = topology.make_mixing(t, n_agents)
    elif isinstance(t, (topology.Topology, topology.TopologyBank)):
        topo = t
    elif callable(t):
        topo = t(n_agents)
    else:
        topo = t
    topo = topology.materialize(topo, name="dist")
    if topo.n != n_agents:
        raise ValueError(
            f"DistConfig.topology has n={topo.n} agents but the mesh's agent "
            f"axes hold {n_agents}")
    return topo


def engine_of(dc: DistConfig, n_agents: int):
    """Resolve DistConfig through the engine_for registry over the config's
    A-agent topology (None for the centralized allreduce reference).  The
    returned engine supplies the trainer's update math (message/apply_stage)
    and its resolved (algorithm, compressor, gossip, topology) tuple —
    print it with core.engines.describe so runs and docs can't silently
    diverge.

    Hypers the engine does not declare raise instead of being silently
    dropped or silently overriding the engine's paper defaults: NIDS for
    example scales its dual ascent by 1/(2 eta) — a gamma passed to it
    would change the algorithm, so it must be rejected loudly."""
    hyp = _hyper_dict(dc)
    if dc.algorithm == "allreduce":
        # LEADHyper is an accepted shape here (the documented LEAD/allreduce
        # convention — gamma/alpha are simply unused); only an explicit dict
        # with keys beyond eta is a contract error
        extra = set(hyp) - {"eta"}
        if extra and not isinstance(dc.hyper, LEADHyper):
            raise ValueError(
                f"allreduce (centralized SGD reference) only takes 'eta'; "
                f"got {sorted(extra)}")
        return None
    declared = _hyper_fields_of(dc.algorithm)
    extra = set(hyp) - declared
    if extra:
        raise ValueError(
            f"algorithm {dc.algorithm!r} does not declare hyper(s) "
            f"{sorted(extra)} (it takes {sorted(declared)}); pass "
            f"DistConfig(hyper={{...}}) with exactly those fields")
    comp = dc.compressor
    if comp is None and not is_exact(dc.algorithm):
        comp = QuantizePNorm(bits=dc.bits, block=dc.block)
    # host-numpy Topology: engine_of may run inside a jitted init trace,
    # where a jnp constant would become a tracer and break validation
    topo = topology_of(dc, n_agents)
    # in_place: the step donates its state, so the apply kernel writes the
    # new state over the old leaves' buffers
    return engine_for(topo, comp, dim=dc.block, interpret=dc.interpret,
                      gossip="neighbor", algorithm=dc.algorithm,
                      faults=dc.faults, in_place=True, **hyp)


def _hyper_fields_of(algorithm: str) -> set:
    """The algorithm hypers (Schedule fields) its engine class declares —
    the same dataclass-fields-minus-layout rule the base's hypers_at
    resolves inside the step, so the two validators cannot diverge."""
    cls = ENGINES[algorithm.lower().replace("_", "-")]
    return {f.name for f in dataclasses.fields(cls)} - set(_LAYOUT_FIELDS)


class TrainState(NamedTuple):
    """All leaves stacked (A, ...): one slice per agent along the ring.

    params is the engine state's iterate x; algo holds the engine's other
    state fields by name (each a pytree shaped like params) — {} for
    single-state algorithms (DGD, QDGD, allreduce)."""
    params: Pytree                       # X — per-agent model replicas
    algo: Dict[str, Pytree]              # engine state fields beyond x
    opt: Any                             # optimizer state (stacked)
    step: jnp.ndarray


def n_agents_of(mesh, prof: shr.ShardingProfile) -> int:
    return int(np.prod([mesh.shape[a] for a in prof.agent_axes]))


def state_shardings(cfg, mesh, prof: shr.ShardingProfile, state_sds):
    """NamedSharding pytree for a TrainState ShapeDtypeStruct tree."""
    del cfg
    return shr.state_shardings_of(mesh, prof, state_sds)


def init_train_state(cfg, mesh, prof: shr.ShardingProfile, dc: DistConfig,
                     key) -> TrainState:
    """Consensus start: every agent holds the same replica, so W x = x
    exactly (W is row-stochastic and all rows are identical) and the
    engine's consensus_init spec materializes each extra state field as a
    copy of the params or zeros — no init communication or gradient needed
    (the paper's X^1 = X^0 - eta g(X^0) warm start is skipped, as every
    trainer algorithm tolerates a plain consensus start)."""
    A = n_agents_of(mesh, prof)
    p0 = tfm.init_params(cfg, key)
    sd = jnp.dtype(dc.state_dtype)

    def stack(l):
        l = l.astype(sd) if jnp.issubdtype(l.dtype, jnp.floating) else l
        return jnp.broadcast_to(l[None], (A,) + l.shape)

    params = tree_map(stack, p0)
    eng = engine_of(dc, A)
    algo = {} if eng is None else {
        f: (params if kind == "copy" else tree_zeros_like(params))
        for f, kind in eng.consensus_init.items()}
    return TrainState(params=params, algo=algo,
                      opt=dc.optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# leaf layout (the kernels' buffers, per stacked leaf)
# ---------------------------------------------------------------------------

def _leaf_view(shape, block: int, comp) -> Optional[tuple]:
    """(rows, width) of a stacked leaf's tiled view, or None for the padded
    path.

    A leaf (A, ..., C) whose last dim C is a multiple of ``block`` (and at
    most quantize.MAX_WIDTH) reaches the engine as (A, rows, C), rows the
    product of the dims between the agent axis and the last (1 for a 1-D
    leaf).  On the TPU an f32 leaf is stored in (8, 128) tiles of its last
    two dims, so this view is a bitcast of it, where rows of ``block``
    taken from its flattening are a copy each way.  (A leaf whose
    second-to-last dim is no multiple of 8, granite's (49155, 2048)
    embedding, is stored in (1, 128) tiles instead and crosses to (8, 128)
    and back on either path.)  Flat block j of the
    leaf is lane segment j % (C / block) of row j // (C / block): the
    block, its scale and its dither are those of the padded path.  A
    compressor whose blocks are not contiguous lane segments (no
    ``row_segments``: RandK's shared-seed mask, TopK's indices) keeps the
    padded path."""
    if comp is not None and not getattr(comp, "row_segments", False):
        return None
    if len(shape) < 2 or shape[-1] % block or shape[-1] > MAX_WIDTH:
        return None
    return int(np.prod(shape[1:-1], dtype=np.int64)), int(shape[-1])


def _leaf_blocks(l: jnp.ndarray, block: int, view: Optional[tuple] = None):
    """Stacked leaf (A, ...) -> (buffer f32, d_leaf): the (A, rows, width)
    view when given, else the flattened leaf zero-padded to (A, nb,
    block)."""
    A = l.shape[0]
    if view is not None:
        return l.reshape((A,) + view).astype(jnp.float32), view[0] * view[1]
    flat = l.reshape(A, -1).astype(jnp.float32)
    d_leaf = flat.shape[1]
    nb = -(-d_leaf // block)
    pad = nb * block - d_leaf
    return jnp.pad(flat, ((0, 0), (0, pad))).reshape(A, nb, block), d_leaf


def _leaf_unblocks(buf: jnp.ndarray, like: jnp.ndarray,
                   view: Optional[tuple] = None) -> jnp.ndarray:
    if view is not None:
        return buf.reshape(like.shape).astype(like.dtype)
    A = like.shape[0]
    flat = buf.reshape(A, -1)[:, :like[0].size]
    return flat.reshape(like.shape).astype(like.dtype)


def _layout_counts(leaves, views) -> Dict[str, int]:
    """Leaves, and elements per agent, on the tiled view and on the padded
    path."""
    out = {"tiled_leaves": 0, "tiled_elements": 0, "padded_leaves": 0,
           "padded_elements": 0}
    for l, v in zip(leaves, views):
        kind = "padded" if v is None else "tiled"
        out[f"{kind}_leaves"] += 1
        out[f"{kind}_elements"] += int(np.prod(l.shape[1:], dtype=np.int64))
    return out


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

def make_train_step(cfg, mesh, prof: shr.ShardingProfile, dc: DistConfig):
    """Returns step(state, batch, key) -> (state, metrics).

    batch: {tokens, labels[, memory]} with leading (A, B_local, ...) dims.
    metrics: grad_norm + (decentralized algorithms) bits_per_agent, the
    actual payload bits this step put on the wire, summed over leaves;
    faulted runs (DistConfig.faults active) additionally report
    dropped_links, the directed gossip edges that did not deliver this
    step.  Hierarchical topologies report leader-lane bits (payload /
    node_size — intra-node traffic is free); interval topologies report
    0.0 bits and 0.0 dropped_links on skipped steps.

    ``step.layout`` counts the model's leaves (and their elements per
    agent) that reach the engine in their tiled view and those on the
    padded path (see _leaf_view); None for allreduce.
    """
    cfg_fwd = cfg
    if dc.seq_parallel and prof.tp_axis and cfg.seq_shard_axis is None:
        cfg_fwd = dataclasses.replace(cfg, seq_shard_axis=prof.tp_axis)
    cdt = jnp.dtype(dc.compute_dtype)
    A = n_agents_of(mesh, prof)
    eng = engine_of(dc, A)
    comp = None if eng is None else eng.compressor
    # the engine already holds the resolved graph — re-resolving through
    # topology_of would hand a non-deterministic DistConfig.topology
    # callable a SECOND, different graph than the one engine_of validated
    topo = eng.topology if eng is not None else topology_of(dc, A)
    # two-level / interval knobs ride on the Topology (core/topology.py):
    # a HierarchicalTopology maps its node blocks onto the TRAILING agent
    # mesh axes (exact pmean inside a node, ppermute only across nodes),
    # and comm_interval = tau gates the whole comm stage on step % tau —
    # skipped steps run the engine's local_stage and ship no collective.
    tau = int(getattr(topo, "comm_interval", 1))
    node_size = int(getattr(topo, "node_size", 1))
    hier = isinstance(topo, topology.HierarchicalTopology) and node_size > 1
    if tau > 1 and eng is None:
        raise ValueError(
            "comm_interval > 1 (Topology.with_interval) gates the "
            "decentralized gossip stage; the centralized allreduce "
            "reference has no gossip stage to skip")
    intra_axes: tuple = ()
    if hier:
        # node blocks are CONSECUTIVE flat agent ids (row-major over the
        # agent axes), so a block is exactly the slice spanned by trailing
        # agent mesh axes whose sizes multiply to node_size — each axis
        # fully inside the block, so lax.pmean over those axes IS the
        # intra-node mean
        rem, taken = node_size, []
        for a in reversed(prof.agent_axes):
            if rem == 1:
                break
            sz = int(mesh.shape[a])
            if rem % sz != 0:
                raise ValueError(
                    f"hierarchical node_size={node_size} must be the "
                    f"product of trailing agent mesh axes (node blocks are "
                    f"consecutive flat agent ids); agent axes "
                    f"{prof.agent_axes} have shapes "
                    f"{[int(mesh.shape[x]) for x in prof.agent_axes]} and "
                    f"axis {a!r} (size {sz}) does not divide the remaining "
                    f"factor {rem}")
            taken.append(a)
            rem //= sz
        if rem != 1:
            raise ValueError(
                f"hierarchical node_size={node_size} exceeds the mesh's "
                f"{A} agents (axes {prof.agent_axes})")
        intra_axes = tuple(reversed(taken))
    # a TopologyBank compiles to ONE step whose gossip schedule is selected
    # per iteration: each bank round graph gets its own static
    # permute_rounds decomposition, and the step's graph (step % P) is
    # picked by lax.switch inside the shard_map — the branch index is the
    # replicated step counter, so every device takes the same branch and
    # the ppermutes inside it stay collective-legal.  A static Topology is
    # the P = 1 case and skips the switch entirely (bit-identical to the
    # pre-bank trainer).
    is_bank = isinstance(topo, topology.TopologyBank)
    if hier:
        # the wire schedule comes from the LANE-WISE inter graph
        # kron(W_inter, I_s): every inter edge (b -> c) ships s parallel
        # ppermutes (b s + i -> c s + i).  On block-constant payloads (the
        # intra pmean runs upstream of encode) lane-wise mixing equals the
        # composite kron(W_inter, J_s / s) mix exactly.  The lane graph is
        # s disjoint copies of the inter graph — validation would reject it
        # as disconnected, but connectivity lives in the intra pmean, so
        # build it unvalidated.
        lane_W = np.kron(topo.inter.W, np.eye(node_size))
        bank_graphs = (topology.from_matrix(
            lane_W, name=f"{topo.name}|lanes", validate=False),)
    else:
        bank_graphs = tuple(topo.rounds) if is_bank else (topo,)
    P_bank = len(bank_graphs)
    # fault injection: an active FaultModel masks the gossip rounds with
    # the same deterministic link_ok realization as the single-device
    # engines (keyed on state.step, so a checkpoint-resumed run sees the
    # identical fault schedule).  src_of[r][j] = the agent j receives from
    # in round r (-1: no edge) — the static arrays the per-step masks are
    # derived from; on a bank the masks compose with the STEP's graph, so
    # only links that exist in round step % P can drop.
    fm = (dc.faults if dc.faults is not None and dc.faults.is_active
          else None)

    def _schedule_of(bt: topology.Topology):
        """One bank round graph -> (permute rounds, per-round receive
        sources, factored-uniform weights or None, per-agent self weight).

        The factored uniform form is valid only when every round is a FULL
        permutation (every agent receives every round — ring, fully
        connected, one-peer exponential): on partial rounds it would add
        the decoded ppermute zero-fill at full weight, silently relying on
        decode(0) == 0.  Graphs with partial rounds (torus with collapsed
        sides, ER) take the per-receiver weighted branch, where rw[idx] ==
        0 masks the fill.  Faulted runs always take the weighted branch —
        the mask substitution is per receiver."""
        rounds = bt.permute_rounds()
        src_of = []
        for pairs, _ in rounds:
            s = np.full((A,), -1, np.int32)
            for i, j in pairs:
                s[j] = i
            src_of.append(s)
        uniform = (bt.uniform_weights
                   if fm is None and all(len(p) == A for p, _ in rounds)
                   else None)
        self_w = bt.weights[:, 0].copy()  # per-agent self weight
        return rounds, src_of, uniform, self_w

    schedules = [_schedule_of(bt) for bt in bank_graphs]
    # per-round receive sources stacked (P, R_max, A) and padded with -1
    # (no edge), so a faulted step can jnp.take the LIVE round's rows by
    # step % P and realize only that graph's link masks — per-step fault
    # work is O(rounds of one graph), not O(sum over the whole bank)
    _r_max = max((len(s[1]) for s in schedules), default=0)
    src_stack = np.full((P_bank, max(_r_max, 1), A), -1, np.int32)
    for _b, (_, _src_of_b, _, _) in enumerate(schedules):
        for _r, _s in enumerate(_src_of_b):
            src_stack[_b, _r] = _s
    axis_name = (prof.agent_axes if len(prof.agent_axes) > 1
                 else prof.agent_axes[0])
    spec = P(prof.agent_axes)            # leading agent axis; rest replicated
    smap = functools.partial(jax.shard_map, mesh=mesh,
                             axis_names=set(mesh.axis_names), check_vma=False)

    def _pperm(tree, pairs):
        """One gossip round: ppermute every payload leaf along the
        flattened agent axes (this IS the inter-agent wire traffic)."""
        return tree_map(
            lambda x: jax.lax.ppermute(x, axis_name, list(pairs)), tree)

    def _agent_index():
        """Flat agent id on the row-major flattened agent axes (matches the
        ppermute pair numbering)."""
        idx = jax.lax.axis_index(prof.agent_axes[0])
        for a in prof.agent_axes[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx

    # -- gradients ----------------------------------------------------------
    def loss_of(p, b):
        if cdt != jnp.float32:
            p = tree_map(lambda l: l.astype(cdt)
                         if jnp.issubdtype(l.dtype, jnp.floating) else l, p)
        return tfm.loss_fn(p, cfg_fwd, b)[0]

    def agent_grad(p, b):
        if dc.microbatches > 1:
            mb = dc.microbatches

            def chunked(l):
                return l.reshape(mb, l.shape[0] // mb, *l.shape[1:])

            chunks = tree_map(chunked, b)

            def accum(acc, bi):
                g = jax.grad(loss_of)(p, bi)
                return tree_map(jnp.add, acc, g), None

            acc, _ = jax.lax.scan(accum, tree_zeros_like(p), chunks)
            return tree_map(lambda l: l / mb, acc)
        return jax.grad(loss_of)(p, b)

    # -- communication stages (the only collectives) ------------------------
    def pmean_tree(tree):
        axis = prof.agent_axes if len(prof.agent_axes) > 1 \
            else prof.agent_axes[0]
        return smap(lambda t: tree_map(
            lambda l: jax.lax.pmean(l, axis), t),
            in_specs=(spec,), out_specs=spec)(tree)

    def pmean_intra(tree):
        """Exact mean over the intra-node mesh axes only (hierarchical
        runs): jnp.mean-class traffic inside a node, which the two-level
        wire accounting counts at zero bits — the inter-node ppermutes in
        gossip_payloads are the only wire traffic."""
        ax = intra_axes if len(intra_axes) > 1 else intra_axes[0]
        return smap(lambda t: tree_map(
            lambda l: jax.lax.pmean(l, ax), t),
            in_specs=(spec,), out_specs=spec)(tree)

    def gossip_payloads(payloads, masks=None, step=None):
        """Per leaf: (q, W q) with q the receiver-decoded own payload and
        W q its neighbor-exchange mix over the STEP's graph — only the
        payload crosses agents (quantizer codes packed into uint32 words
        when wire_pack).  Exact algorithms ship {"values": raw_leaf} with
        identity decode — the uncompressed ppermute exchange.

        The collective schedule is Topology.permute_rounds(): one ppermute
        per partial permutation of directed edges, decoded at the receiver
        and combined with that round's receiver weight.  Uniform-weight
        graphs whose rounds are all FULL permutations (ring, fully
        connected, one-peer exponential) take the factored `w_self * own +
        w_nb * sum(rounds)` form — for the ring (rounds = the classic
        fwd/bwd pair) this is expression-for-expression the pre-Topology
        ppermute path, so its trajectories are bit-identical.  Everything
        else (metropolis weights, or partial rounds like the torus's wrap
        edges) looks its per-receiver round weight up by
        jax.lax.axis_index — a receiver with no edge in a round gets
        ppermute's zero fill, masked by rw[idx] == 0 regardless of what
        decode makes of the fill.

        BOTH q and wq are decoded inside the one shard_map, from the same
        materialized payload operand.  Decoding q from a second copy of the
        encode outside the shard_map would let XLA re-derive it in a
        different fusion context, and the two floor() evaluations can then
        disagree on knife-edge elements — the own-decode and the wire would
        carry different codes.

        ``masks`` (faulted runs only) is the (R_max, A) bool link_ok
        realization for the STEP's round graph — already selected by
        step % P at the caller, so the step never realizes masks for the
        P - 1 graphs it does not exchange; replicated across the mesh,
        row r read by the branch's gossip round r (padding rows beyond a
        graph's own round count are never read).  A receiver whose
        round-r link dropped substitutes its OWN decoded payload for the
        undelivered one at the round's weight — exactly
        faults.renormalize_*'s mass-to-self degradation, so the realized
        mixing stays row-stochastic (and doubly stochastic for the
        symmetric link-drop masks LEAD needs).

        ``step`` (TopologyBank runs only) is the replicated iteration
        counter: lax.switch(step % P) selects the graph's branch, whose
        ppermutes are the static schedule of that round graph.  Static
        topologies never pass it — their call path (and jaxpr) is the
        pre-bank one."""
        def mix_one(sched, own, wire, dec, msks):
            rounds, _, uniform, self_w = sched
            if not rounds:                           # single agent: W = [1]
                return own
            if uniform is not None:
                w_self, w_nb = uniform
                acc = None
                for pairs, _ in rounds:
                    recv = dec(_pperm(wire, pairs))
                    acc = recv if acc is None else acc + recv
                return w_self * own + w_nb * acc
            idx = _agent_index()
            wq = jnp.asarray(self_w, own.dtype)[idx] * own
            for r, (pairs, rw) in enumerate(rounds):
                recv = dec(_pperm(wire, pairs))
                if msks is not None:
                    recv = jnp.where(msks[r][idx], recv, own)
                wq = wq + jnp.asarray(rw, own.dtype)[idx] * recv
            return wq

        def body(pls, msks=None, stp=None):
            outs = []
            for pl in pls:
                if dc.wire_pack and "code" in pl:
                    code_shape = pl["code"].shape    # local (1, rows, width)

                    def dec(w, shape=code_shape):
                        code = unpack_codes(w["packed"], int(np.prod(shape)),
                                            comp.bits).reshape(shape)
                        return comp.decode_blocks(
                            {"code": code, "scale": w["scale"]},
                            interpret=dc.interpret)

                    wire = {"packed": pack_codes(pl["code"], comp.bits),
                            "scale": pl["scale"]}
                else:
                    wire = pl
                    dec = (functools.partial(comp.decode_blocks,
                                             interpret=dc.interpret)
                           if comp is not None else (lambda w: w["values"]))
                own = dec(wire)
                if P_bank == 1:
                    wq = mix_one(schedules[0], own, wire, dec, msks)
                else:
                    # msks (if any) already holds the live round's masks;
                    # branch b only runs when step % P == b, so every
                    # branch reads the same selected rows
                    branches = [
                        functools.partial(
                            lambda sched, o, w: mix_one(sched, o, w,
                                                        dec, msks),
                            sched)
                        for sched in schedules]
                    wq = jax.lax.switch(
                        jnp.asarray(stp, jnp.int32) % P_bank, branches,
                        own, wire)
                outs.append((own, wq))
            return outs

        if masks is None and step is None:
            return smap(lambda pls: body(pls),
                        in_specs=(spec,), out_specs=spec)(payloads)
        if masks is None:
            return smap(lambda pls, stp: body(pls, None, stp),
                        in_specs=(spec, P()),
                        out_specs=spec)(payloads, step)
        if step is None:
            return smap(lambda pls, mk: body(pls, mk),
                        in_specs=(spec, P()),
                        out_specs=spec)(payloads, masks)
        return smap(body, in_specs=(spec, P(), P()),
                    out_specs=spec)(payloads, masks, step)

    def encode_payloads(keys, msgs, dims):
        """Each wire's (payload, bits per agent), as lists.  A row-segment
        operator (QuantizePNorm, Identity) encodes inside a shard_map, each
        device its own agents with the keys encode_blocks would split for
        them: its quantize kernel is a Pallas call, which XLA cannot
        partition over the agent axis.  Other operators encode the whole
        agent axis (encode_blocks); exact algorithms ship the raw buffer."""
        if comp is None:
            return ([{"values": m} for m in msgs],
                    [jnp.asarray(d * 32, jnp.float32) for d in dims])
        if not getattr(comp, "row_segments", False):
            out = [comp.encode_blocks(kk, m, d, interpret=dc.interpret)
                   for kk, m, d in zip(keys, msgs, dims)]
            return [p for p, _ in out], [b for _, b in out]

        def body(agent_keys, bufs):
            out = [comp.encode_agents(ks, m, d, interpret=dc.interpret)
                   for ks, m, d in zip(agent_keys, bufs, dims)]
            return [p for p, _ in out], [b for _, b in out]

        agent_keys = [jax.random.split(kk, A) for kk in keys]
        return smap(body, in_specs=(spec, spec),
                    out_specs=(spec, P()))(agent_keys, msgs)

    # -- the step -----------------------------------------------------------
    # Each stage of the step runs under a named scope (stage.grad,
    # stage.encode, stage.gossip, stage.apply — the flat engines use the
    # same names): XLA keeps it as a segment of every op's op_name
    # metadata, so a profiler trace can put each device op down to its
    # stage.  Metadata only: the compiled program is the same without it.
    def step(state: TrainState, batch: Dict[str, jnp.ndarray], key):
        with jax.named_scope("stage.grad"):
            g = jax.vmap(agent_grad)(state.params, batch)
            g = tree_map(lambda l: l.astype(jnp.float32), g)
            direction, opt_state = dc.optimizer.update(g, state.opt,
                                                       state.params)
            gnorm = jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2)
                                 for l in jax.tree_util.tree_leaves(
                                     direction)))
        metrics = {"grad_norm": gnorm}

        if eng is None:                  # centralized allreduce reference
            eta = _at(_hyper_dict(dc).get("eta", _DEFAULT_ETA), state.step)
            g_avg = pmean_tree(direction)
            x_new = tree_map(lambda xl, gl: xl - eta * gl,
                             state.params, g_avg)
            return TrainState(params=x_new, algo=state.algo, opt=opt_state,
                              step=state.step + 1), metrics

        # engine substrate over stacked leaves: blockify -> message ->
        # [intra-node pmean] -> encode -> gossip (shard_map) -> apply_stage
        # -> [intra-node state projection] -> unblockify.  comm_interval >
        # 1 gates the whole middle on step % tau: skipped steps run the
        # engine's local_stage instead — no encode, no collective, zero
        # wire bits.
        hy = eng.hypers_at(state.step)
        leaves_x, treedef = jax.tree_util.tree_flatten(state.params)
        leaves_g = treedef.flatten_up_to(direction)
        leaves_algo = {f: treedef.flatten_up_to(state.algo[f])
                       for f in eng.consensus_init}
        keys = jax.random.split(key, max(len(leaves_x), 1))

        views = [_leaf_view(lx.shape, dc.block, comp) for lx in leaves_x]
        states, gbs, d_leafs = [], [], []
        with jax.named_scope("stage.encode"):
            for i, (lx, lg, v) in enumerate(zip(leaves_x, leaves_g, views)):
                xb, d_leaf = _leaf_blocks(lx, dc.block, v)
                gb, _ = _leaf_blocks(lg, dc.block, v)
                fields = {f: _leaf_blocks(leaves_algo[f][i], dc.block, v)[0]
                          for f in leaves_algo}
                states.append(eng.state_cls(x=xb, k=state.step, **fields))
                gbs.append(gb)
                d_leafs.append(d_leaf)

        def _unblock(new_states):
            new_x = [_leaf_unblocks(ns.x, lx, v)
                     for ns, lx, v in zip(new_states, leaves_x, views)]
            new_algo = {f: [_leaf_unblocks(getattr(ns, f), lx, v)
                            for ns, lx, v in zip(new_states, leaves_x, views)]
                        for f in leaves_algo}
            return new_x, new_algo

        def comm(_):
            # multi-wire engines (eng.wire_fields beyond one entry — C-GT
            # ships an iterate payload AND a tracker payload) flatten into
            # the same per-leaf pipeline: the message list holds n_wires
            # consecutive entries per leaf (leaf-major order), each wire j
            # encoding under fold_in(leaf_key, j) — the engine's own
            # multi-wire stream, so simulator and trainer draws agree —
            # and gossip_payloads exchanges every flat entry unchanged.
            # bits_total sums over (leaf x wire): both buffers really
            # cross the wire each exchange.
            with jax.named_scope("stage.encode"):
                n_wires = eng.n_wires
                msgs, ctxs, wire_keys, wire_dims = [], [], [], []
                for kk, s_leaf, gb, d_leaf in zip(keys, states, gbs,
                                                  d_leafs):
                    msg, ctx = eng.message(s_leaf, gb, hy)
                    wires = msg if n_wires > 1 else (msg,)
                    assert len(wires) == n_wires, (eng.wire_fields,
                                                   len(wires))
                    msgs.extend(wires)
                    wire_keys.extend([kk] if n_wires == 1 else
                                     [jax.random.fold_in(kk, j)
                                      for j in range(n_wires)])
                    wire_dims.extend([d_leaf] * n_wires)
                    ctxs.append(ctx)
                if hier:
                    # exact block mean BEFORE encode: each node quantizes
                    # one shared message (per-lane dither — see
                    # gossip_payloads)
                    msgs = pmean_intra(msgs)
                payloads, wire_bits = encode_payloads(wire_keys, msgs,
                                                      wire_dims)
                bits_total = jnp.zeros((), jnp.float32)
                for bits in wire_bits:
                    bits_total = bits_total + bits

            with jax.named_scope("stage.gossip"):
                masks = None
                dropped = jnp.zeros((), jnp.float32)
                if fm is not None:
                    # (R_max, A) survival masks for the LIVE round graph
                    # only: select the step's receive sources first (step %
                    # P), then realize the counter-hash link_ok over them —
                    # same realization the simulator uses (keyed on
                    # state.step — replayable across restarts and
                    # checkpoints), but the hash and reduction work never
                    # touches the P-1 graphs that are not exchanged this
                    # step.  Padded rows (src -1) are masked by `present`, so
                    # dropped_links counts real edges of round step % P
                    # alone; on interval runs the whole block sits inside the
                    # comm branch, so skipped steps realize (and report) no
                    # faults at all.
                    src_sel = (jnp.asarray(src_stack[0]) if P_bank == 1
                               else jnp.take(jnp.asarray(src_stack),
                                             state.step % P_bank, axis=0))
                    present = src_sel >= 0
                    masks = fm.link_ok(state.step, src_sel,
                                       jnp.arange(A)) & present
                    dropped = jnp.sum(present & ~masks).astype(jnp.float32)
                q_wqs = gossip_payloads(
                    payloads, masks, step=state.step if P_bank > 1 else None)
                if n_wires > 1:
                    # regroup the flat (leaf x wire) results back to one
                    # (q-tuple, wq-tuple) pair per leaf — the shape
                    # apply_stage expects from a multi-wire engine
                    q_wqs = [(tuple(q for q, _ in q_wqs[i:i + n_wires]),
                              tuple(wq for _, wq in q_wqs[i:i + n_wires]))
                             for i in range(0, len(q_wqs), n_wires)]

            with jax.named_scope("stage.apply"):
                def apply_leaves(sts, gs, qws, cs, h):
                    return [eng.apply_stage(s_leaf, gb, q, wq, h, ctx)[0]
                            for s_leaf, gb, (q, wq), ctx
                            in zip(sts, gs, qws, cs)]

                if is_bank:
                    # a bank's apply stage re-mixes H with the step's
                    # graph, so it needs the whole agent axis
                    new_states = apply_leaves(states, gbs, q_wqs, ctxs, hy)
                else:
                    # every static-graph apply stage is per-agent algebra:
                    # run it on each device's own agents, where a Pallas
                    # kernel (which XLA cannot partition) sees a local buffer
                    args = (states, gbs, q_wqs, ctxs, hy)
                    specs = tree_map(lambda l: spec if jnp.ndim(l) else P(),
                                     args)
                    new_states = smap(apply_leaves, in_specs=specs,
                                      out_specs=specs[0])(*args)
                new_x, new_algo = _unblock(new_states)
                if hier:
                    # project the FULL state back to block-constant — each
                    # node is one logical agent (P W = W P keeps LEAD's hw =
                    # W h invariant) — and count leader-lane bits only: the s
                    # lanes of a node carry one logical payload each round
                    new_x = pmean_intra(new_x)
                    new_algo = {f: pmean_intra(ls)
                                for f, ls in new_algo.items()}
                    bits_total = bits_total / node_size
            return new_x, new_algo, bits_total, dropped

        def local(_):
            with jax.named_scope("stage.apply"):
                new_states = [eng.local_stage(s_leaf, gb, hy)[0]
                              for s_leaf, gb in zip(states, gbs)]
                new_x, new_algo = _unblock(new_states)
            zero = jnp.zeros((), jnp.float32)
            return new_x, new_algo, zero, zero

        if tau == 1:
            # branch-free: jaxpr identical to the pre-interval trainer
            new_x, new_algo, bits_total, dropped = comm(None)
        else:
            new_x, new_algo, bits_total, dropped = jax.lax.cond(
                state.step % tau == 0, comm, local, None)

        metrics["bits_per_agent"] = bits_total
        if fm is not None:
            metrics["dropped_links"] = dropped
        new = TrainState(
            params=jax.tree_util.tree_unflatten(treedef, new_x),
            algo={f: jax.tree_util.tree_unflatten(treedef, ls)
                  for f, ls in new_algo.items()},
            opt=opt_state, step=state.step + 1)
        if finite_checks_enabled():
            assert_finite_tree({"params": new.params, "metrics": metrics},
                               where="dist train step")
        return new, metrics

    # the layout each leaf of the model takes, counted once from the
    # parameters' shapes (None: allreduce has no engine buffers)
    step.layout = None
    if eng is not None:
        replica = jax.eval_shape(functools.partial(tfm.init_params, cfg),
                                 jax.ShapeDtypeStruct((2,), jnp.uint32))
        stacked = [jax.ShapeDtypeStruct((A,) + l.shape, l.dtype)
                   for l in jax.tree_util.tree_leaves(replica)]
        step.layout = _layout_counts(
            stacked, [_leaf_view(l.shape, dc.block, comp) for l in stacked])
    return step

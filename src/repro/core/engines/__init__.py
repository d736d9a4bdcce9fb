"""Flat engine family: scan-compiled codes-on-the-wire substrate for every
paper algorithm.

    base.py       shared substrate (block layout, encode/decode wire stage,
                  dense|ring gossip, payload-bit accounting, fast dither)
    lead.py       FlatLEADEngine — the fused-kernel LEAD hot path
    baselines.py  flat twins of every baseline: CHOCO-SGD, DeepSqueeze,
                  QDGD, DCD-SGD (compressed) and DGD, NIDS, EXTRA, D2
                  (exact, no encode stage)
    cedas.py      FlatCEDASEngine — compressed exact diffusion [Huang & Pu
                  2023]; the first engine built for the time-varying
                  TopologyBank path (mixes with the step's round graph)
    cgt.py        FlatCGTEngine — compressed gradient tracking [Liao et
                  al. 2022]; the first MULTI-WIRE engine (iterate +
                  tracker payloads per exchange), stable on the directed
                  one-peer banks that break LEAD/CEDAS

``engine_for`` is the registry front door: it dispatches
``(algorithm, compressor, topology)`` to the matching engine — the first
argument is a first-class ``core/topology.Topology`` (ring, torus_2d,
erdos_renyi, from_matrix, ...; raw matrices are normalized) and ``gossip``
selects dense or sparse neighbor-exchange mixing over it — so the whole
Fig. 2-4 sweep runs on the flat substrate with byte-accurate wire bits on
any Assumption-1 graph.
``flat_twin`` builds the flat engine mirroring a tree baseline instance
(same W, compressor, and hyper-parameters) — the one-line migration path
for drivers that hold core/baselines.py objects.  ``describe`` renders the
resolved (algorithm, compressor, gossip) triple as one line — examples and
the launch drivers print it so runs and docs can't silently diverge.

The registry serves two substrates with one math implementation per
algorithm: the single-device scan simulator drives engines directly
(core/simulator.py run()), and the multi-host trainer (dist/trainer.py)
drives the same engines' message/apply stages per stacked model leaf with
shard_map ring gossip in between.  Hyper-parameters are Schedule values
(floats or callables of k — Theorem 2), resolved inside the scan.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.engines.base import FlatEngineBase, fast_uniform
from repro.core.engines.baselines import (
    ExtraState, FlatCHOCOEngine, FlatD2Engine, FlatDCDEngine, FlatDGDEngine,
    FlatDeepSqueezeEngine, FlatEXTRAEngine, FlatNIDSEngine, FlatQDGDEngine,
)
from repro.core.engines.cedas import FlatCEDASEngine
from repro.core.engines.cgt import FlatCGTEngine
from repro.core.engines.lead import FlatLEADEngine, FlatLEADState
from repro.kernels.ops import DEFAULT_BLOCK

# registry: algorithm name -> engine class (aliases share one class)
ENGINES = {
    "lead": FlatLEADEngine,
    "cedas": FlatCEDASEngine,
    "cgt": FlatCGTEngine,
    "c-gt": FlatCGTEngine,
    "choco": FlatCHOCOEngine,
    "choco-sgd": FlatCHOCOEngine,
    "deepsqueeze": FlatDeepSqueezeEngine,
    "qdgd": FlatQDGDEngine,
    "dcd": FlatDCDEngine,
    "dcd-sgd": FlatDCDEngine,
    "dgd": FlatDGDEngine,
    "nids": FlatNIDSEngine,
    "extra": FlatEXTRAEngine,
    "d2": FlatD2Engine,
}

# exact baselines take no compressor (their payload is the raw buffer)
_EXACT = (FlatDGDEngine, FlatNIDSEngine, FlatEXTRAEngine, FlatD2Engine)

# canonical name per engine class (first registry entry wins over aliases)
_CANONICAL = {}
for _name, _cls in ENGINES.items():
    _CANONICAL.setdefault(_cls, _name)
del _name, _cls


def is_exact(algorithm: str) -> bool:
    """True when the registered algorithm transmits raw 32-bit values (the
    exact baselines, which take no compressor)."""
    key = algorithm.lower().replace("_", "-")
    if key not in ENGINES:
        raise KeyError(f"unknown algorithm {algorithm!r}; registry has "
                       f"{sorted(set(ENGINES))}")
    return issubclass(ENGINES[key], _EXACT)


def algorithm_name(engine) -> str:
    """Canonical registry key of an engine instance (aliases collapse)."""
    return _CANONICAL[type(engine)]


def describe(engine) -> str:
    """One-line `(algorithm, compressor, gossip, topology)` description of a
    resolved engine — the registry path a run actually took.  Printed by the
    examples and launch drivers (and asserted by tests/test_docs.py) so docs
    snippets and real runs stay in sync."""
    comp = engine.compressor
    comp_s = "none (exact, 32-bit)" if comp is None else repr(comp)
    return (f"algorithm={algorithm_name(engine)} compressor={comp_s} "
            f"gossip={engine.gossip} topology={engine.topology!r}")

# tree-class name (core/baselines.py) -> registry key, for flat_twin
_TREE_TWINS = {
    "CEDAS": "cedas",
    "CGT": "cgt",
    "CHOCO_SGD": "choco",
    "DeepSqueeze": "deepsqueeze",
    "QDGD": "qdgd",
    "DCD_SGD": "dcd",
    "DGD": "dgd",
    "NIDS": "nids",
    "EXTRA": "extra",
    "D2": "d2",
}


def engine_for(topology, compressor, dim: int,
               interpret: Optional[bool] = None,
               dither: str = "match", gossip: str = "dense",
               algorithm: str = "lead", faults=None, in_place: bool = False,
               **hyper) -> FlatEngineBase:
    """Registry dispatch: (algorithm, compressor, topology) -> flat engine.

    `topology` is a core/topology.Topology — built by topology.ring(n),
    torus_2d(...), erdos_renyi(...), from_matrix(W), ... — or a raw mixing
    matrix, normalized through topology.as_topology.  `gossip` selects the
    communication stage over it: "dense" (W @ q matmul) or "neighbor"
    (sparse neighbor-exchange gather over the topology's padded table,
    O(n * deg * d), any Assumption-1 graph); "ring" is the historical alias
    for neighbor exchange that asserts the topology IS the uniform ring.

    Every shipped compressor runs flat on every compressed algorithm: the
    p=inf QuantizePNorm through LEAD's fused kernels (or its encode_blocks
    path on the baselines), Identity through the exact no-encode shortcut,
    and everything else (RandK, TopK, p != inf quantizers) through its
    encode_blocks wire path.  Only an object without that protocol is
    rejected.  `dither` selects the quantizer dither stream for every
    engine's fused p=inf path ("match" = tree-equivalent threefry, "fast" =
    counter-hash); `hyper` forwards algorithm hyper-parameters to the
    engine's fields (eta/gamma for the baselines; eta/gamma/alpha for LEAD
    — which LEADSim instead overrides with a LEADHyper per step — and for
    CEDAS).  Every hyper
    is a Schedule — a float or a callable of the iteration counter k
    (Theorem 2 diminishing stepsizes), resolved inside the scan — so the
    Fig. 3 stochastic sweep runs on the flat path for every algorithm.
    Every returned engine is directly drivable by core/simulator.py run().

    `faults` attaches a core/faults.FaultModel: drivers then route the
    communication stage through the engine's masked-mixing path
    (step_with_wire_faulted) with deterministic, replayable link drops,
    agent dropout, stragglers, and payload corruption.  None (the default)
    leaves the clean path untouched.  `in_place` lets the apply stage's
    kernel write the new state over the old one's buffers (for callers that
    donate the state; see FlatEngineBase).
    """
    from repro.core.compression import Identity

    key = algorithm.lower().replace("_", "-")
    if key not in ENGINES:
        raise KeyError(f"unknown algorithm {algorithm!r}; registry has "
                       f"{sorted(set(ENGINES))}")
    cls = ENGINES[key]

    if isinstance(compressor, Identity):
        compressor = None
    if issubclass(cls, _EXACT) and compressor is not None:
        raise ValueError(f"{cls.__name__} is an exact baseline; it does not "
                         "take a compressor")
    if compressor is not None and not hasattr(compressor, "encode_blocks"):
        raise NotImplementedError(
            f"{type(compressor).__name__} lacks the encode_blocks/"
            "decode_blocks flat wire protocol; use engine='tree'")

    block = getattr(compressor, "block", DEFAULT_BLOCK)
    return cls(topology=topology, dim=dim, compressor=compressor, block=block,
               interpret=interpret, gossip=gossip, dither=dither,
               faults=faults, in_place=in_place, **hyper)


def flat_twin(algo, dim: int, *, gossip: str = "dense",
              interpret: Optional[bool] = None) -> FlatEngineBase:
    """Flat engine mirroring a tree baseline instance from core/baselines.py
    — same mixing matrix, compressor, and hyper-parameters, ready to hand to
    core/simulator.py run() in its place."""
    name = type(algo).__name__
    if name not in _TREE_TWINS:
        raise KeyError(f"no flat twin registered for {name}; registry has "
                       f"{sorted(_TREE_TWINS)}")
    cls = ENGINES[_TREE_TWINS[name]]
    fields = {f.name for f in dataclasses.fields(cls)}
    hyper = {k: getattr(algo, k) for k in ("eta", "gamma", "alpha")
             if k in fields and hasattr(algo, k)}
    # most tree baselines hold a DenseGossip; CEDAS holds a first-class
    # topology (possibly a TopologyBank) — hand either to engine_for
    topo = (algo.gossip.W if hasattr(algo, "gossip") else algo.topology)
    return engine_for(topo, getattr(algo, "compressor", None), dim,
                      interpret=interpret, gossip=gossip,
                      algorithm=_TREE_TWINS[name], **hyper)

"""Baseline decentralized algorithms the paper compares against (§2, §5).

All baselines are written in the simulator representation: the iterate X is a
single (n, d) array (n agents, d coordinates), the mixing is a DenseGossip,
and stochastic gradients arrive as an (n, d) array evaluated at the current X.

Implemented (source in brackets):
  * DGD / D-PSGD           [Nedic & Ozdaglar 2009; Lian et al. 2017]
  * NIDS                   [Li, Shi, Yan 2019] — two-step form, eqs. (4)-(5)
  * EXTRA                  [Shi et al. 2015]
  * D2                     [Tang et al. 2018b] — eq. (15)
  * CHOCO-SGD              [Koloskova et al. 2019]
  * DeepSqueeze            [Tang et al. 2019a]
  * QDGD                   [Reisizadeh et al. 2019a]
  * DCD-SGD                [Tang et al. 2018a]
  * CEDAS                  [Huang & Pu 2023, arXiv:2301.05872] — compressed
                           exact diffusion; the one baseline built for
                           time-varying graphs, so it holds a Topology /
                           TopologyBank instead of a DenseGossip and mixes
                           with the *step's* round graph W_{k mod P}

Each algorithm exposes  init(x0, g0, key) -> state  and
step(state, g, key) -> state, where g = grad F(state.x; xi).  Every
hyper-parameter (eta, gamma) is a ``Schedule`` — a float or a callable of
the iteration counter k (core/lead.py `_at`; the Theorem 2
diminishing-stepsize mode) — resolved at ``state.k`` inside each step, so
the Fig. 3 stochastic sweeps drive the baselines with the same schedule
objects as LEAD.  A uniform
`state.x` field holds the current iterates so drivers can be generic.  The
compressed algorithms additionally expose
step_with_metrics(state, g, key) -> (state, comp_err) with comp_err the
*exact in-step* relative compression error of the quantity the algorithm
transmitted this iteration (the Trace convention in core/simulator.py) —
CHOCO: x_half - xhat, DeepSqueeze: the error-compensated v = x - eta g + e,
QDGD: x, DCD: the post-gossip x - xhat.

Engine-family representation: every algorithm here also has a *flat twin*
in core/engines/baselines.py running on the scan-compiled codes-on-the-wire
substrate — state in the kernels' (n, nb, block) block layout, the encoded
payload as the only cross-agent traffic (dense or ring gossip), and actual
per-step payload bits.  The classes in this module are the tree references
those engines are tested against (tests/test_flat_baselines.py); build a
twin with core.engines.flat_twin(algo, dim).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.compression import rel_err as _rel_err
from repro.core.gossip import HIGHEST, DenseGossip
from repro.core.lead import Schedule, _at


class SimpleState(NamedTuple):
    x: jnp.ndarray
    k: jnp.ndarray


class PrevGradState(NamedTuple):
    x: jnp.ndarray
    x_prev: jnp.ndarray
    g_prev: jnp.ndarray
    k: jnp.ndarray


class HatState(NamedTuple):
    x: jnp.ndarray
    xhat: jnp.ndarray        # public (quantized) copies, one per agent
    xhat_w: jnp.ndarray      # sum_j w_ij xhat_j, tracked incrementally
    k: jnp.ndarray


class ErrorState(NamedTuple):
    x: jnp.ndarray
    e: jnp.ndarray           # error-compensation memory
    k: jnp.ndarray


class DualState(NamedTuple):
    x: jnp.ndarray
    d: jnp.ndarray
    k: jnp.ndarray


class DiffusionState(NamedTuple):
    x: jnp.ndarray
    psi_prev: jnp.ndarray    # previous adapt half-step psi = x - eta g
    h: jnp.ndarray           # public (compressed-tracking) copies
    hw: jnp.ndarray          # mixed public copies (see CEDAS docstring)
    k: jnp.ndarray


class TrackingState(NamedTuple):
    """C-GT state: the iterate wire AND the gradient-tracker wire, each with
    its own error-feedback reference pair (see CGT docstring).  The tracker
    is stored in SHIFTED form: ``s`` holds the post-mix tracker of the last
    step and ``g_prev`` the gradient it already incorporates, so the live
    tracker of step k is ``s + g_k - g_prev`` and the stored invariant is
    ``sum_i s_i == sum_i g_prev_i`` (exactly preserved by doubly stochastic
    realized mixing)."""
    x: jnp.ndarray
    s: jnp.ndarray           # gradient tracker (shifted: pre-refresh)
    g_prev: jnp.ndarray      # gradient already folded into s
    h_x: jnp.ndarray         # iterate wire: public copies
    hw_x: jnp.ndarray        # iterate wire: mixed public copies
    h_s: jnp.ndarray         # tracker wire: public copies
    hw_s: jnp.ndarray        # tracker wire: mixed public copies
    k: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DGD:
    """Decentralized gradient descent: X+ = W X - eta g (no compression)."""
    gossip: DenseGossip
    eta: Schedule = 0.1

    def init(self, x0, g0, key):
        return SimpleState(x=x0, k=jnp.zeros((), jnp.int32))

    def step(self, s: SimpleState, g, key):
        x = self.gossip.mix(s.x) - _at(self.eta, s.k) * g
        return SimpleState(x=x, k=s.k + 1)


@dataclasses.dataclass(frozen=True)
class NIDS:
    """NIDS two-step primal-dual form (paper eqs. (4)-(5))."""
    gossip: DenseGossip
    eta: Schedule = 0.1

    def init(self, x0, g0, key):
        x1 = x0 - _at(self.eta, jnp.zeros((), jnp.int32)) * g0
        d1 = jnp.zeros_like(x0)
        return DualState(x=x1, d=d1, k=jnp.zeros((), jnp.int32))

    def step(self, s: DualState, g, key):
        eta = _at(self.eta, s.k)
        y = s.x - eta * g - eta * s.d
        d = s.d + self.gossip.i_minus_w(y) / (2.0 * eta)
        x = s.x - eta * g - eta * d
        return DualState(x=x, d=d, k=s.k + 1)


@dataclasses.dataclass(frozen=True)
class EXTRA:
    """EXTRA [Shi et al. 2015]:
    X^{k+2} = (I+W) X^{k+1} - Wtilde X^k - eta (g^{k+1} - g^k),
    Wtilde = (I+W)/2."""
    gossip: DenseGossip
    eta: Schedule = 0.1

    def init(self, x0, g0, key):
        x1 = self.gossip.mix(x0) - _at(self.eta, jnp.zeros((), jnp.int32)) * g0
        return PrevGradState(x=x1, x_prev=x0, g_prev=g0, k=jnp.zeros((), jnp.int32))

    def step(self, s: PrevGradState, g, key):
        Wx = self.gossip.mix(s.x)
        Wtx_prev = 0.5 * (s.x_prev + self.gossip.mix(s.x_prev))
        x = s.x + Wx - Wtx_prev - _at(self.eta, s.k) * (g - s.g_prev)
        return PrevGradState(x=x, x_prev=s.x, g_prev=g, k=s.k + 1)


@dataclasses.dataclass(frozen=True)
class D2:
    """D2 [Tang et al. 2018b], paper eq. (15):
    X^{k+1} = (I+W)/2 (2 X^k - X^{k-1} - eta g^k + eta g^{k-1})."""
    gossip: DenseGossip
    eta: Schedule = 0.1

    def init(self, x0, g0, key):
        x1 = x0 - _at(self.eta, jnp.zeros((), jnp.int32)) * g0
        return PrevGradState(x=x1, x_prev=x0, g_prev=g0, k=jnp.zeros((), jnp.int32))

    def step(self, s: PrevGradState, g, key):
        eta = _at(self.eta, s.k)
        inner = 2.0 * s.x - s.x_prev - eta * g + eta * s.g_prev
        x = 0.5 * (inner + self.gossip.mix(inner))
        return PrevGradState(x=x, x_prev=s.x, g_prev=g, k=s.k + 1)


@dataclasses.dataclass(frozen=True)
class CHOCO_SGD:
    """CHOCO-SGD [Koloskova et al. 2019].

    x_half = x - eta g
    q      = Q(x_half - xhat_self)                    (difference compression)
    xhat  += q   (all agents update their public copies with received q)
    x+     = x_half + gamma * (W xhat - xhat_self)    (quantized gossip)
    """
    gossip: DenseGossip
    compressor: Any
    eta: Schedule = 0.1
    gamma: Schedule = 0.8

    def init(self, x0, g0, key):
        xhat = jnp.zeros_like(x0)
        return HatState(x=x0, xhat=xhat, xhat_w=self.gossip.mix(xhat),
                        k=jnp.zeros((), jnp.int32))

    def step_with_metrics(self, s: HatState, g, key):
        """(new_state, comp_err): comp_err = ||q - (x_half - xhat)|| /
        ||x_half||, the error of the message this step transmitted."""
        x_half = s.x - _at(self.eta, s.k) * g
        diff = x_half - s.xhat
        keys = jax.random.split(key, s.x.shape[0])
        q = jax.vmap(self.compressor.compress)(keys, diff)
        xhat = s.xhat + q
        xhat_w = s.xhat_w + self.gossip.mix(q)
        x = x_half + _at(self.gamma, s.k) * (xhat_w - xhat)
        new = HatState(x=x, xhat=xhat, xhat_w=xhat_w, k=s.k + 1)
        return new, _rel_err(q, diff, x_half)

    def step(self, s: HatState, g, key):
        return self.step_with_metrics(s, g, key)[0]


@dataclasses.dataclass(frozen=True)
class CEDAS:
    """CEDAS [Huang & Pu 2023, arXiv:2301.05872]: compressed exact diffusion.

    psi  = x - eta g                      (adapt)
    phi  = psi + x - psi_prev             (exact-diffusion correction)
    q    = Q(phi - h)                     (difference compression; the wire)
    h+   = h + alpha q
    hw+  = hw + alpha W q                 (static W — incremental, hw == W h)
         = W_k h + alpha W_k q            (TopologyBank — the step's graph)
    x+   = phi + (gamma/2) (hw+ - h+);  psi_prev+ = psi

    With Identity compression and alpha = gamma = 1 the recursion collapses
    to exact diffusion — D2's eq. (15) with Wtilde = (I+W)/2
    (tests/test_cedas.py pins the reduction against the rolled-out D2
    recursion).  Unlike the other baselines this one holds a first-class
    ``topology`` (Topology | TopologyBank | matrix | scheduled Topology,
    normalized through core/topology.materialize) rather than a DenseGossip:
    on a bank every step mixes with the round graph W_{k mod P}, and ``hw``
    is recomputed from the step's graph instead of tracked incrementally —
    under time-varying W the incremental sum accumulates alpha W_j q over
    PAST round graphs and the hw == W h invariant (hence convergence) is
    lost.  Measured on n=32 least squares, 4-bit quantization,
    random_matching(32) bank, gamma=0.25, alpha=1: recomputed hw reaches
    consensus to 3e-14 where the incremental form stalls at O(1).

    Stability over time-varying graphs needs per-round SYMMETRIC mixing
    (e.g. random_matching): the diffusion momentum phi = 2x - psi_prev
    composed with *directed* rounds (exponential_onepeer's complex
    eigenvalues) has joint spectral radius > 1 at every gamma — measured
    ~1.04/step on exponential_onepeer(32) even uncompressed.  LEAD's
    engine-side W_k recompute (engines/lead.py) is the combination that
    converges on directed one-peer banks.
    """
    topology: Any
    compressor: Any
    eta: Schedule = 0.1
    gamma: Schedule = 0.5
    alpha: Schedule = 0.5

    def __post_init__(self):
        from repro.core import topology as _topo
        object.__setattr__(self, "topology",
                           _topo.materialize(self.topology, name="matrix"))

    @property
    def _bank(self) -> bool:
        from repro.core import topology as _topo
        return isinstance(self.topology, _topo.TopologyBank)

    def _mix(self, v, k):
        """W_{k mod P} @ v on a bank (traced round slice), W @ v otherwise."""
        if self._bank:
            r = jnp.asarray(k, jnp.int32) % self.topology.period
            W = jnp.asarray(self.topology.Ws, v.dtype)[r]
        else:
            W = jnp.asarray(self.topology.W, v.dtype)
        return jnp.matmul(W, v, precision=HIGHEST)

    def init(self, x0, g0, key):
        return DiffusionState(x=x0, psi_prev=x0, h=x0,
                              hw=self._mix(x0, jnp.zeros((), jnp.int32)),
                              k=jnp.zeros((), jnp.int32))

    def step_with_metrics(self, s: DiffusionState, g, key):
        """(new_state, comp_err): comp_err = ||q - (phi - h)|| / ||phi||,
        the error of the compressed diffusion message this step."""
        eta = _at(self.eta, s.k)
        gamma = _at(self.gamma, s.k)
        alpha = _at(self.alpha, s.k)
        psi = s.x - eta * g
        phi = psi + s.x - s.psi_prev
        diff = phi - s.h
        keys = jax.random.split(key, s.x.shape[0])
        q = jax.vmap(self.compressor.compress)(keys, diff)
        h = s.h + alpha * q
        wq = self._mix(q, s.k)
        if self._bank:
            hw = self._mix(s.h, s.k) + alpha * wq
        else:
            hw = s.hw + alpha * wq
        x = phi + 0.5 * gamma * (hw - h)
        new = DiffusionState(x=x, psi_prev=psi, h=h, hw=hw, k=s.k + 1)
        return new, _rel_err(q, diff, phi)

    def step(self, s: DiffusionState, g, key):
        return self.step_with_metrics(s, g, key)[0]


@dataclasses.dataclass(frozen=True)
class CGT:
    """C-GT [Liao et al., arXiv:2205.12623]: compressed gradient tracking.

    Two tracked sequences cross the wire every step — the iterate x and the
    gradient tracker y — each through its own CHOCO-style difference
    compression with an error-feedback reference pair (h, hw).  Per agent,
    with y_k = s + g_k - g_prev the live tracker (see TrackingState):

        q_x  = Q(x - h_x);   q_s = Q(y - h_s)          (the two wires)
        x̂   = h_x + q_x;    x̂_w = hw_x + W q_x        (static W)
                             x̂_w = W_k (h_x + q_x)     (TopologyBank)
        ŝ   = h_s + q_s;    ŝ_w analogous
        x+   = x - gamma (x̂ - x̂_w) - eta y
        s+   = y - gamma (ŝ - ŝ_w);   g_prev+ = g
        h+   = h + alpha q;  hw+ = hw + alpha W q       (each wire;
                             hw+ = W_k (h + alpha q) on a bank)

    The tracking invariant ``sum_i s_i == sum_i g_prev_i`` (equivalently
    sum of live trackers == sum of gradients) holds at every step for any
    compression whenever the realized mixing is column-stochastic — doubly
    stochastic W, or symmetric link-drop masks under the renormalize fault
    policy.  With Identity compression the recursion collapses to exact
    lazy gradient tracking, x+ = M_gamma x - eta y and y+ = M_gamma y +
    g+ - g with M_gamma = (1-gamma) I + gamma W — DIGing / Aug-DGM at
    gamma = 1 (tests/test_cgt.py pins the reduction for every gamma).

    Like CEDAS this reference holds a first-class ``topology`` (Topology |
    TopologyBank | matrix), mixing with the step's round graph W_{k mod P}
    on a bank and recomputing both hw pairs from the step's graph.  Unlike
    LEAD/CEDAS, whose dual/momentum pairs go unstable through directed
    one-peer rounds past n~16 (ARCHITECTURE §4a), C-GT's consensus pair is
    block-triangular in (x, y) with per-round factors M_k that are convex
    combinations of row-stochastic matrices — the period monodromy radius
    never exceeds 1, and on exponential_onepeer(2^m) the period product at
    gamma = 1 is exact uniform averaging (measured + pinned in
    tests/test_cgt.py and BENCH_baselines.json).

    Randomness contract: wire j draws with jax.random.fold_in(key, j) then
    the per-agent split — exactly the flat engine's multi-wire dither
    stream, so flat-vs-tree stays draw-for-draw.
    """
    topology: Any
    compressor: Any
    eta: Schedule = 0.05
    gamma: Schedule = 0.5
    alpha: Schedule = 0.5

    def __post_init__(self):
        from repro.core import topology as _topo
        object.__setattr__(self, "topology",
                           _topo.materialize(self.topology, name="matrix"))

    @property
    def _bank(self) -> bool:
        from repro.core import topology as _topo
        return isinstance(self.topology, _topo.TopologyBank)

    def _mix(self, v, k):
        """W_{k mod P} @ v on a bank (traced round slice), W @ v otherwise."""
        if self._bank:
            r = jnp.asarray(k, jnp.int32) % self.topology.period
            W = jnp.asarray(self.topology.Ws, v.dtype)[r]
        else:
            W = jnp.asarray(self.topology.W, v.dtype)
        return jnp.matmul(W, v, precision=HIGHEST)

    def init(self, x0, g0, key):
        z = jnp.zeros_like(x0)
        return TrackingState(x=x0, s=z, g_prev=z, h_x=x0,
                             hw_x=self._mix(x0, jnp.zeros((), jnp.int32)),
                             h_s=z, hw_s=z, k=jnp.zeros((), jnp.int32))

    def _compress(self, key, j, diff):
        """Wire j's compression draw (fold_in(key, j) then per-agent split
        — the flat engine's multi-wire stream)."""
        keys = jax.random.split(jax.random.fold_in(key, j), diff.shape[0])
        return jax.vmap(self.compressor.compress)(keys, diff)

    def step_with_metrics(self, s: TrackingState, g, key):
        """(new_state, comp_err): comp_err reports the ITERATE wire,
        ||q_x - (x - h_x)|| / ||x|| (the Trace convention's transmitted
        iterate; the tracker wire's error enters the trajectory but not the
        scalar metric)."""
        eta = _at(self.eta, s.k)
        gamma = _at(self.gamma, s.k)
        alpha = _at(self.alpha, s.k)
        y = s.s + g - s.g_prev                  # live tracker at step k
        diff_x = s.x - s.h_x
        diff_s = y - s.h_s
        q_x = self._compress(key, 0, diff_x)
        q_s = self._compress(key, 1, diff_s)
        wq_x = self._mix(q_x, s.k)
        wq_s = self._mix(q_s, s.k)
        xhat = s.h_x + q_x
        shat = s.h_s + q_s
        if self._bank:
            wh_x = self._mix(s.h_x, s.k)
            wh_s = self._mix(s.h_s, s.k)
            xhat_w = wh_x + wq_x
            shat_w = wh_s + wq_s
            hw_x = wh_x + alpha * wq_x
            hw_s = wh_s + alpha * wq_s
        else:
            xhat_w = s.hw_x + wq_x
            shat_w = s.hw_s + wq_s
            hw_x = s.hw_x + alpha * wq_x
            hw_s = s.hw_s + alpha * wq_s
        x = s.x - gamma * (xhat - xhat_w) - eta * y
        s_new = y - gamma * (shat - shat_w)
        new = TrackingState(x=x, s=s_new, g_prev=g,
                            h_x=s.h_x + alpha * q_x, hw_x=hw_x,
                            h_s=s.h_s + alpha * q_s, hw_s=hw_s, k=s.k + 1)
        return new, _rel_err(q_x, diff_x, s.x)

    def step(self, s: TrackingState, g, key):
        return self.step_with_metrics(s, g, key)[0]


@dataclasses.dataclass(frozen=True)
class DeepSqueeze:
    """DeepSqueeze [Tang et al. 2019a]: error-compensated direct compression.

    v   = x - eta g + e          (compensate last step's compression error)
    c   = Q(v);  e+ = v - c      (store new error)
    x+  = c + gamma * (W c - c)  (gossip on the compressed models)
    """
    gossip: DenseGossip
    compressor: Any
    eta: Schedule = 0.1
    gamma: Schedule = 0.2

    def init(self, x0, g0, key):
        return ErrorState(x=x0, e=jnp.zeros_like(x0), k=jnp.zeros((), jnp.int32))

    def step_with_metrics(self, s: ErrorState, g, key):
        """(new_state, comp_err): the transmitted message is the
        error-compensated v = x - eta g + e, NOT the raw iterate —
        comp_err = ||c - v|| / ||v||."""
        v = s.x - _at(self.eta, s.k) * g + s.e
        keys = jax.random.split(key, s.x.shape[0])
        c = jax.vmap(self.compressor.compress)(keys, v)
        e = v - c
        x = c + _at(self.gamma, s.k) * (self.gossip.mix(c) - c)
        return ErrorState(x=x, e=e, k=s.k + 1), _rel_err(c, v, v)

    def step(self, s: ErrorState, g, key):
        return self.step_with_metrics(s, g, key)[0]


@dataclasses.dataclass(frozen=True)
class QDGD:
    """QDGD [Reisizadeh et al. 2019a]: direct quantized model exchange.

    x+ = x + gamma * (W Q(x) - Q_self(x)) ... - eta g
    (each agent transmits Q(x_i); receives neighbors' quantized models).
    """
    gossip: DenseGossip
    compressor: Any
    eta: Schedule = 0.1
    gamma: Schedule = 0.2

    def init(self, x0, g0, key):
        return SimpleState(x=x0, k=jnp.zeros((), jnp.int32))

    def step_with_metrics(self, s: SimpleState, g, key):
        """(new_state, comp_err): comp_err = ||q - x|| / ||x|| for the
        directly-transmitted quantized model."""
        keys = jax.random.split(key, s.x.shape[0])
        q = jax.vmap(self.compressor.compress)(keys, s.x)
        x = (s.x + _at(self.gamma, s.k) * (self.gossip.mix(q) - q)
             - _at(self.eta, s.k) * g)
        return SimpleState(x=x, k=s.k + 1), _rel_err(q, s.x, s.x)

    def step(self, s: SimpleState, g, key):
        return self.step_with_metrics(s, g, key)[0]


@dataclasses.dataclass(frozen=True)
class DCD_SGD:
    """DCD-SGD [Tang et al. 2018a]: difference compression of the update.

    x+    = W xhat_local_view - eta g   with xhat the public copies
    q     = Q(x+ - xhat_self); xhat += q
    (unstable under aggressive compression — reproduced as in the paper.)
    """
    gossip: DenseGossip
    compressor: Any
    eta: Schedule = 0.1

    def init(self, x0, g0, key):
        return HatState(x=x0, xhat=x0, xhat_w=self.gossip.mix(x0),
                        k=jnp.zeros((), jnp.int32))

    def step_with_metrics(self, s: HatState, g, key):
        """(new_state, comp_err): comp_err = ||q - (x+ - xhat)|| / ||x+||
        for the compressed difference of the post-gossip iterate."""
        x = s.xhat_w - _at(self.eta, s.k) * g
        diff = x - s.xhat
        keys = jax.random.split(key, s.x.shape[0])
        q = jax.vmap(self.compressor.compress)(keys, diff)
        xhat = s.xhat + q
        xhat_w = s.xhat_w + self.gossip.mix(q)
        new = HatState(x=x, xhat=xhat, xhat_w=xhat_w, k=s.k + 1)
        return new, _rel_err(q, diff, x)

    def step(self, s: HatState, g, key):
        return self.step_with_metrics(s, g, key)[0]

"""Flat-buffer LEAD engine: the fused-kernel hot path of the simulator.

The pytree path (core/lead.py) touches every parameter element with ~12
separate elementwise ops per iteration (Alg. 1 lines 4-7) — each an HBM
round trip on a memory-bound update.  This engine keeps the LEAD state as
contiguous ``(n_agents, nb, block)`` f32 buffers in the kernels' native
block layout (see kernels/__init__.py for the layout contract) and runs the
iteration as exactly two fused passes:

  * pre-communication — fused Y-difference + encode.  For the p=inf
    quantizer this is kernels.lead_update.lead_diff_encode (one read of
    (X, G, D, H, dither), one write of int8 codes + per-block scales); every
    other operator goes through its ``encode_blocks`` flat wire path (see
    core/compression.py), one XLA-fused pass over the same buffers.
  * kernels.lead_update.lead_update — post-communication: fused
    H / H_w / D / X update, one read of (X, G, D, H, H_w, Qh, WQh), one
    write of the four new state buffers.

The two passes are expressed as the engine family's stage protocol
(engines/base.py): ``encode_stage`` (overridden here to fuse message +
encode for the p=inf quantizer) and ``apply_stage`` (the lead_update
kernel).  Both are shape-polymorphic over any blocked buffers, so
dist/trainer.py drives the *same* LEAD math per stacked model leaf with
shard_map ring gossip in between — one implementation, simulator and
multi-host trainer alike.

Codes on the wire
-----------------
Layout, wire protocol, and gossip stage come from the engine-family base
(engines/base.py): between the two passes only the *payload* exists, mixed
either densely (W @ decode) or by sparse neighbor exchange over the
engine's Topology (any Assumption-1 graph).  ``step_wire``
additionally returns the bits each agent put on the wire this step, computed
from the actual payload (data-dependent for RandK) — the byte-accurate
x-axis of the paper's Fig. 1b/6, replacing static ``wire_bits(d)`` estimates.

Bit-compatibility with the tree path
------------------------------------
The engine draws per-operator randomness exactly the way
``simulator.vmap_compress`` does — one key per agent via
``jax.random.split``, draws over the *logical* per-agent shape — and the
fused kernels use the same left-to-right subtraction order as ``lead.step``,
so ``engine="flat"`` and ``engine="tree"`` produce matching ``LEADState``
trajectories for every shipped compressor (tests/test_engine.py asserts
atol <= 1e-5 over 20 steps).  Zero rows are a fixed point of both passes,
so the tile padding past the logical blocks never leaks into the trajectory.
``dither="fast"`` (fused quantizer path only) swaps the threefry dither for
the counter-hash generator in engines/base.py — statistically equivalent,
much cheaper, but a different random stream.

Time-varying banks
------------------
With a TopologyBank the engine mixes with the step's round graph
W_{k mod P} and RECOMPUTES H_w from it (see apply_stage) — required for
convergence, since the incremental H_w sum would mix past rounds' graphs.
Stability is a property of the bank, measured in tests/test_cedas.py and
docs/ARCHITECTURE.md §4a: LEAD reaches consensus on directed one-peer
exponential banks up to n = 16 (gamma = 1) and on symmetric
random_matching banks at n = 32 (gamma <~ 0.3), but on
exponential_onepeer(32) the dual recursion's period monodromy exceeds
radius 1 at every gamma — no hyper-parameter converges there.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.engines.base import FlatEngineBase, _is_fused_quantizer
from repro.core.lead import LEADHyper, Schedule, _at
from repro.kernels import lead_update as _lu


class FlatLEADState(NamedTuple):
    """LEAD state in the kernels' block layout: all buffers (n, nb, block)
    f32, zero-padded past the logical dimension d."""
    x: jnp.ndarray
    h: jnp.ndarray
    hw: jnp.ndarray
    d: jnp.ndarray
    k: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class FlatLEADEngine(FlatEngineBase):
    """init/step over flat buffers; mirrors core/lead.py semantics exactly.

    compressor=None runs Identity (Qh = Y - H, no encode stage).  The p=inf
    QuantizePNorm takes the fused diff+encode kernel; every other operator
    (RandK, TopK, p != inf) goes through its encode_blocks wire path.

    dither="match" draws the quantizer dither exactly as the tree path does
    (per-agent threefry; trajectories match engine="tree" bit for bit modulo
    compiler rounding).  dither="fast" uses the counter-hash generator in
    engines/base.py — statistically equivalent, much cheaper, but a
    different random stream, so trajectories equal the tree path's only in
    distribution.  It applies to the fused quantizer path; other operators
    always draw threefry inside encode_blocks (their cost is not
    dither-dominated).

    Two driving modes.  LEADSim passes a LEADHyper per call (init/step/
    step_wire); alternatively the engine stores its own hypers (eta/gamma/
    alpha fields, the paper's defaults) and then follows the family's
    baseline driver protocol — init(x0, g0, key) / step_with_wire(state, g,
    key) — so ``engine_for(W, comp, d)`` hands core/simulator.py run() a
    directly drivable engine like every other registry entry.  In both
    modes every hyper is a Schedule: a float or a callable of the iteration
    counter k (Theorem 2 diminishing stepsizes), resolved inside the scan.
    """
    eta: Schedule = 0.1
    gamma: Schedule = 1.0
    alpha: Schedule = 0.5

    state_cls = FlatLEADState
    consensus_init = {"h": "copy", "hw": "copy", "d": "zeros"}

    @property
    def hyper(self) -> LEADHyper:
        """The stored hypers, for the per-call-hyper entry points."""
        return LEADHyper(eta=self.eta, gamma=self.gamma, alpha=self.alpha)

    # -- algorithm ---------------------------------------------------------
    def init(self, x0: jnp.ndarray, g0: jnp.ndarray,
             hyper=None) -> FlatLEADState:
        """Paper init: X^1 = X^0 - eta0 g(X^0); H^1 = X^0; H_w^1 = W H^1;
        D^1 = 0.  x0, g0: (n, d).  `hyper` is a LEADHyper; any other value
        (e.g. the driver protocol's PRNG key) selects the stored hypers."""
        if not isinstance(hyper, LEADHyper):
            hyper = self.hyper
        eta0 = _at(hyper.eta, jnp.zeros((), jnp.int32))
        xb, gb = self.blockify(x0), self.blockify(g0)
        h1 = xb
        return FlatLEADState(x=xb - eta0 * gb, h=h1, hw=self._mix(h1),
                             d=jnp.zeros_like(xb),
                             k=jnp.zeros((), jnp.int32))

    # -- stage protocol ------------------------------------------------------
    def message(self, s: FlatLEADState, gb, hy):
        """Pre-communication difference Y - H (Alg. 1 line 4 + COMM line 10);
        ctx is unused — apply_stage recomputes Y (XLA CSEs the shared ops)."""
        y = s.x - hy["eta"] * gb - hy["eta"] * s.d
        return y - s.h, None

    def encode_stage(self, s: FlatLEADState, gb, key, hy):
        """For the fused p=inf quantizer the Y-difference and the encode
        happen in one kernel pass; other compressors compute the difference
        in XLA and go through the base's message + encode_payload path.
        The hier wire also takes the base path: the node's intra-mean must
        happen between the difference and the encode, so the fused
        per-agent diff+encode kernel does not apply."""
        comp = self.compressor
        if comp is not None and _is_fused_quantizer(comp) and not self._hier:
            code, scale = _lu.lead_diff_encode(
                self._rows(s.x), self._rows(gb), self._rows(s.d),
                self._rows(s.h),
                self._rows(self._dither_plane(key, s.k)),
                hy["eta"], bits=comp.bits, interpret=self.interpret)
            payload, decode, bits = self.quant_payload(code, scale, comp.bits)
            return payload, decode, bits, None
        return super().encode_stage(s, gb, key, hy)

    def apply_stage(self, s: FlatLEADState, gb, qh, wqh, hy, ctx=None):
        """Post-communication fused H / H_w / D / X update (lines 5-7) plus
        the exact in-step comp_err ||Qh - (Y-H)|| / ||Y||.  Shape-derived
        rows (the kernel fits its tile to them) so the same kernel call
        serves the engine's own padded buffers and the trainer's per-leaf
        blocks."""
        if self._bank:
            # Time-varying graphs break the incremental invariant
            # hw == W h that static LEAD maintains for free (hw would
            # accumulate alpha W_j q over PAST round graphs, and the dual
            # integrates the drift with gamma/(2 eta) gain — divergence).
            # Recompute the mixed public estimate with the STEP's graph:
            # the fused kernel computes yh_w = hw + wqh, so feeding it the
            # effective innovation (W_k h + wqh) - hw yields exactly
            # yh_w = W_k (h + qh).  H is reference state, not wire traffic
            # (receivers hold replicas in a real deployment), so this mix
            # is clean even on the faulted path.
            wqh = self.mix_round(s.h, s.k) + wqh - s.hw
        xo, do, ho, hwo = _lu.lead_update(
            self._rows(s.x), self._rows(gb), self._rows(s.d),
            self._rows(s.h), self._rows(s.hw), self._rows(qh),
            self._rows(wqh), hy["eta"], hy["gamma"], hy["alpha"],
            interpret=self.interpret)
        shape3 = s.x.shape
        new = FlatLEADState(x=xo.reshape(shape3), d=do.reshape(shape3),
                            h=ho.reshape(shape3), hw=hwo.reshape(shape3),
                            k=s.k + 1)
        y = s.x - hy["eta"] * gb - hy["eta"] * s.d
        return new, self.rel_err(qh, y - s.h, y)

    def local_stage(self, s: FlatLEADState, gb, hy):
        """Interval (no-communication) step: X advances by its full primal
        direction -eta (g + D) while the communication trackers H / H_w / D
        freeze — no payload was produced, so the public estimate and the
        dual see nothing.  At the consensual optimum D = -g(x*), so this
        local step fixes x* exactly: tau > 1 preserves LEAD's exact fixed
        point (unlike local-SGD baselines, which pick up an O(eta tau)
        heterogeneity bias)."""
        x = s.x - hy["eta"] * gb - hy["eta"] * s.d
        return (FlatLEADState(x=x, h=s.h, hw=s.hw, d=s.d, k=s.k + 1),
                jnp.zeros((), jnp.float32))

    # -- per-call-hyper entry points (LEADSim) -------------------------------
    def step_wire(self, state: FlatLEADState, g: jnp.ndarray, key: jax.Array,
                  hyper=None):
        """One LEAD iteration on flat buffers; g: gradients at state.x,
        either (n, d) (blockified here) or already (n, nb, block) — the
        engine's native layout, which skips the per-step padding copy.
        `hyper` defaults to the engine's stored hypers.

        Returns (new_state, comp_err, wire_bits):
          comp_err  = ||Qh - (Y-H)|| / ||Y||, the compression error this
                      step incurred;
          wire_bits = bits per agent on the wire this step, from the actual
                      payload.
        jit callers that drop a metric get its extra passes DCE'd."""
        if not isinstance(hyper, LEADHyper):
            hyper = self.hyper
        hy = {f: _at(getattr(hyper, f), state.k)
              for f in ("eta", "gamma", "alpha")}
        return self._step_core(state, g, key, hy)

    def step_with_wire(self, state: FlatLEADState, g, key: jax.Array):
        """Baseline driver protocol (engines/base.py) with stored hypers."""
        return self.step_wire(state, g, key, self.hyper)

    def step(self, state: FlatLEADState, g: jnp.ndarray, key: jax.Array,
             hyper=None) -> FlatLEADState:
        """The family's uniform step: the new state alone (metrics and wire
        accounting are DCE'd under jit; use step_wire to keep them)."""
        return self.step_wire(state, g, key, hyper)[0]

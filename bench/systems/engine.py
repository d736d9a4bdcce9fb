"""Flat-engine cells: ``engine_for(...)``'s LEAD engine driven as
core/simulator.run drives it, ``step_with_wire`` inside one jitted
``lax.scan`` of K steps with the state donated, called until the window
ends.

Set-up draws the problem from the seed, takes the engine's own init and
its first call of K steps, which compiles and warms the window's program
and is kept for the comparison with the plain reference.  After the window
the program's state is freed and the reference (bench/reference/lead.py
over a dense W) retraces those K steps from the seed.
"""
from __future__ import annotations

import functools
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts
from bench.generators import quadratic
from bench.reference import lead as ref_lead
from bench.systems import quiet_host

TRACED_CALLS = 1


def _keys(key):
    """(problem, step) keys of a run."""
    return jax.random.fold_in(key, 0), jax.random.fold_in(key, 1)


def hypers(config: dict) -> dict:
    """eta = 1 / a_max, the bound of the curvatures the oracle draws (a
    constant, so the compiled program does not depend on the seed);
    gamma and alpha as the configuration states."""
    o, alg = config["oracle"], config["algorithm"]
    return {"eta": 1.0 / o["a_max"], "gamma": alg["gamma"],
            "alpha": alg["alpha"]}


class Program:
    """The engine under test, its problem and its state."""

    def __init__(self, config: dict, traffic: dict, seed_key):
        from repro.core import topology
        from repro.core.compression import QuantizePNorm
        from repro.core.engines import engine_for

        alg, o = config["algorithm"], config["oracle"]
        if alg["topology"] != "ring":
            raise NotImplementedError("the reference mixes over a ring")
        if traffic["generator"] != "quadratic":
            raise ValueError(f"the engine reads quadratic traffic, not "
                             f"{traffic['generator']!r}")
        self.n, self.dim, self.block = (config["agents"], config["d"],
                                        alg["block"])
        if self.dim % self.block:
            raise ValueError("d must be a whole number of blocks")
        self.rows = self.dim // self.block
        self.K = traffic["steps_per_call"]
        hy = hypers(config)
        self.eng = engine_for(topology.ring(self.n),
                              QuantizePNorm(bits=alg["bits"],
                                            block=self.block),
                              self.dim, gossip=alg["gossip"],
                              dither=alg["dither"], **hy)
        self.nb = self.eng.nb
        self.pkey, self.skey = _keys(seed_key)
        a, b, x0 = quadratic.problem(self.pkey, agents=self.n,
                                     rows=self.rows, block=self.block,
                                     a_min=o["a_min"], a_max=o["a_max"])
        eng = self.eng
        self.a = a
        self.b = jax.jit(lambda b: eng.blockify(b.reshape(self.n, -1)))(b)
        self.state = jax.jit(lambda x0, a, b: eng.init(
            x0.reshape(self.n, -1),
            quadratic.grad(a, b, x0).reshape(self.n, -1), None))(x0, a, b)
        del b, x0

        K = self.K

        def call(state, a, b, key):
            def body(s, _):
                g = quadratic.grad(a, b, s.x)
                new, _, bits = eng.step_with_wire(
                    s, g, quadratic.step_key(key, s.k))
                return new, bits
            return jax.lax.scan(body, state, None, length=K)

        self.call_fn = jax.jit(call, donate_argnums=0)

    def call(self):
        """K steps; returns the per-step wire bits (device array)."""
        self.state, bits = self.call_fn(self.state, self.a, self.b,
                                        self.skey)
        return bits

    def free(self):
        for a in jax.tree_util.tree_leaves((self.state, self.a, self.b)):
            a.delete()
        self.state = self.a = self.b = None


@functools.partial(jax.jit, static_argnames=("n", "rows", "block"))
def _snapshot(x, h, hw, d, *, n, rows, block):
    """The state's logical (n, d) fields, copied out of the donated
    buffers."""
    return tuple(f.reshape(n, -1)[:, :rows * block].astype(jnp.float32)
                 for f in (x, h, hw, d))


def _dist_to_opt(x, a, b):
    """max_i ||x_i - x*|| for the quadratic's minimiser x*; x is (n, d) or
    (n, rows, block), a (n, 1, 1), b (n, rows, block)."""
    xs = quadratic.x_star(a, b).reshape(1, -1)
    diff = x.reshape(x.shape[0], -1) - xs
    return jnp.max(jnp.sqrt(jnp.sum(jnp.square(diff), axis=1)))


class RefState(NamedTuple):
    x: jnp.ndarray
    h: jnp.ndarray
    hw: jnp.ndarray
    d: jnp.ndarray
    k: jnp.ndarray


class Reference:
    """The plain LEAD recursion from the seed over a dense W, computed in
    ``dtype``: the paper's init (X1 = X0 - eta g(X0), H = X0, H_w = W X0,
    D = 0), then K steps a call.  It has the program's interface, so in
    ``dtype`` bfloat16 it stands in the program's place as the control."""

    def __init__(self, config: dict, traffic: dict, seed_key,
                 dtype=jnp.float32):
        alg, o = config["algorithm"], config["oracle"]
        self.n, self.dim, self.block = (config["agents"], config["d"],
                                        alg["block"])
        self.rows = self.dim // self.block
        self.K = traffic["steps_per_call"]
        self.nb = self.rows
        n, rows, block, K = self.n, self.rows, self.block, self.K
        hy = hypers(config)
        pkey, skey = _keys(seed_key)
        W = jnp.asarray(ref_lead.ring(n), jnp.float32)
        self.a, self.b, x0 = quadratic.problem(
            pkey, agents=n, rows=rows, block=block, a_min=o["a_min"],
            a_max=o["a_max"])
        wire = counts.quantizer_wire_bits(self.dim, alg["bits"], block)

        @jax.jit
        def init(a, b, x0):
            g0 = quadratic.grad(a, b, x0)
            hw = jnp.tensordot(W, x0, axes=([1], [0]),
                               precision=ref_lead.HIGHEST)
            return RefState((x0 - hy["eta"] * g0).astype(dtype),
                            x0.astype(dtype), hw.astype(dtype),
                            jnp.zeros_like(x0, dtype),
                            jnp.zeros((), jnp.int32))

        def call(state, a, b):
            def body(s, _):
                g = quadratic.grad(a.astype(dtype), b.astype(dtype), s.x)
                u = ref_lead.dither(quadratic.step_key(skey, s.k), n, rows,
                                    block)
                new = ref_lead.step(s.x, g, s.h, s.hw, s.d, u, W,
                                    eta=hy["eta"], gamma=hy["gamma"],
                                    alpha=hy["alpha"], bits=alg["bits"],
                                    dtype=dtype)
                return RefState(*new, s.k + 1), jnp.float32(wire)
            return jax.lax.scan(body, state, None, length=K)

        self.state = init(self.a, self.b, x0)
        del x0
        self.call_fn = jax.jit(call, donate_argnums=0)

    def call(self):
        self.state, bits = self.call_fn(self.state, self.a, self.b)
        return bits

    def free(self):
        for a in jax.tree_util.tree_leaves((self.state, self.a, self.b)):
            a.delete()
        self.state = self.a = self.b = None


def reference_fields(config: dict, traffic: dict, seed_key) -> tuple:
    """The state after the reference's first call, as (n, d) f32 fields."""
    ref = Reference(config, traffic, seed_key)
    ref.call()
    s = ref.state
    return _snapshot(s.x, s.h, s.hw, s.d, n=ref.n, rows=ref.rows,
                     block=ref.block)


def measure(cell, seed_key, seconds: float, tracer=None,
            program=None) -> dict:
    """Set-up, the window, the traced calls (when ``tracer``), the memory
    peak and the comparison with the reference.  ``program`` stands in
    for the engine (the control)."""
    config, traffic = cell.config, cell.traffic
    prog = (program or Program)(config, traffic, seed_key)
    n, rows, block = prog.n, prog.rows, prog.block
    want_bits = counts.quantizer_wire_bits(prog.dim,
                                           config["algorithm"]["bits"],
                                           block)
    dist = jax.jit(lambda x, a, b: _dist_to_opt(
        x.reshape(n, -1)[:, :prog.dim], a,
        b.reshape(n, -1)[:, :prog.dim].reshape(n, rows, block)))
    dist0 = float(dist(prog.state.x, prog.a, prog.b))
    bits = prog.call()
    s = prog.state
    first = _snapshot(s.x, s.h, s.hw, s.d, n=n, rows=rows, block=block)
    bits_gap = float(np.max(np.abs(np.asarray(bits, np.float64)
                                   - want_bits)))
    jax.block_until_ready((prog.state, first))
    t_setup_end = time.perf_counter()
    clock = cell.compile_clock
    compiles_before = clock.count()

    calls = failed = 0
    pending = None
    with quiet_host():
        t0 = time.perf_counter()
        while True:
            bits = prog.call()
            calls += 1
            if pending is not None:
                failed += int(np.any(np.asarray(pending) != want_bits))
            pending = bits
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(prog.state)
        window_s = time.perf_counter() - t0
    failed += int(np.any(np.asarray(pending) != want_bits))
    compiles_in_window = clock.count() - compiles_before
    steps = calls * prog.K
    result = {"setup_end": t_setup_end, "window_s": window_s,
              "steps": steps, "attempted": steps, "failed": failed * prog.K,
              "end_to_end": {"engine_steps_per_s": steps / window_s},
              "compiles_in_window": compiles_in_window}
    if tracer is not None:
        def traced():
            for _ in range(TRACED_CALLS):
                with jax.profiler.TraceAnnotation("call"):
                    prog.call()
            jax.block_until_ready(prog.state)
        result["trace"] = tracer(traced)
        result["traced_steps"] = TRACED_CALLS * prog.K
    dist1 = float(dist(prog.state.x, prog.a, prog.b))
    k_end = int(prog.state.k)
    result["memory_peak_bytes"] = max(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for dev in jax.devices()[:cell.chips])
    R = n * prog.nb
    result["context"] = {
        "lead_update_bytes_per_step_per_chip": counts.lead_update_bytes(
            R, block),
        "lead_update_calls_per_step_per_chip": 1,
        "diff_encode_bytes_per_step_per_chip": counts.diff_encode_bytes(
            R, block),
        "diff_encode_calls_per_step_per_chip": 1,
    }
    prog.free()
    t_ref = time.perf_counter()
    first = tuple(np.asarray(f) for f in first)
    ref_fields = tuple(np.asarray(f) for f in reference_fields(
        config, traffic, seed_key))
    calls_made = 1 + calls + (TRACED_CALLS if tracer is not None else 0)
    result["values"] = compare.engine_values(first, ref_fields,
                                             dist1 / dist0, bits_gap)
    result["checks"] = compare.checks(result["values"], cell.limits) + [
        ("steps_counted_gap", float(abs(k_end - calls_made * prog.K)), 0.0),
        ("compiles_in_window", float(compiles_in_window), 0.0)]
    result["phases"] = {"window": window_s,
                        "after_window": t_ref - t0 - window_s,
                        "reference": time.perf_counter() - t_ref}
    return result

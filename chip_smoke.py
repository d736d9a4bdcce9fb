"""Proof that the main path runs on a TPU: python chip_smoke.py

One chip (the default) runs two phases in this one process:

  train   the decentralized trainer through repro.launch.train's own
          build/loop: granite-3-2b at published widths cut to 4 layers, one
          agent on mesh 1,1, LEAD with a 2-bit wire, f32 state, seq_len 1024,
          batch 2 per agent, 5 steps.  Loss and grad_norm must be finite at
          every step, bits_per_agent must equal QuantizePNorm.wire_bits summed
          over the leaves, and the step must compile exactly once.
  engine  the flat LEAD engine (n=8 agents, d=2**24) with the compiled
          Pallas kernels, each step checked against the same step under the
          jnp backend on the same chip; then LEAD's apply stage and the
          fused diff+encode kernel at granite-3-2b's embedding (196,620
          rows, so the last grid step is a partial block) against the jnp
          backend; then core.simulator.run on a well-posed least-squares
          problem (n=8, d=16384) at the engine's own precision, whose
          distance to the optimum must fall by at least 1e3.

``--four-chips`` runs only the path that exists across chips: the same
trainer on mesh 4,1 (four agents, one per chip, gossip over ICI ppermute),
with the embedding and every layer-0 leaf checked against a
dense-W reference computed on the host, step by step from a re-synced
state, and LEAD's dual invariant sum_i d_i = 0 checked over every leaf.

The script fails (non-zero exit, no result line) when JAX finds no TPU, when
the kernels would not run as compiled Pallas, or when any check fails.  The
last line of stdout is the JSON result; everything else comes before it.
Set JAX_COMPILATION_CACHE_DIR to keep compiled programs somewhere else than
<repo>/.jax_cache.
"""
import argparse
import contextlib
import functools
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
if not (HERE / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke.py: no src/repro next to {HERE}; run it from a "
             "checkout of the repository")
sys.path.insert(0, str(HERE / "src"))

import jax                                               # noqa: E402
import jax.numpy as jnp                                  # noqa: E402
import numpy as np                                       # noqa: E402

from repro.core import simulator, topology               # noqa: E402
from repro.core.compression import (QuantizePNorm,       # noqa: E402
                                    _stochastic_quantize)
from repro.core.convex import HIGHEST, LinearRegression  # noqa: E402
from repro.core.engines import engine_for                # noqa: E402
from repro.dist.trainer import DistConfig, engine_of     # noqa: E402
from repro.kernels import dispatch                       # noqa: E402
from repro.kernels.lead_update import lead_diff_encode   # noqa: E402
from repro.kernels.quantize import fit_tile              # noqa: E402
from repro.launch import train as train_mod              # noqa: E402
from repro.models import transformer as tfm              # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_secs = []


def _on_duration(event, duration, **_):
    if event in _COMPILE_EVENTS:
        _compile_secs.append(duration)


def say(phase, **fields):
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def require_chip(count=None):
    """The device facts for the result line; raises unless JAX runs on TPU
    and the kernels resolve to compiled Pallas."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: jax.devices()[0].platform is "
                           f"{devs[0].platform!r}")
    backend = dispatch.default_backend()
    if backend != "pallas":
        raise RuntimeError(f"kernel backend resolves to {backend!r}, not "
                           f"'pallas' (is {dispatch._ENV_VAR} set?)")
    if count is not None and len(devs) < count:
        raise RuntimeError(f"need {count} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs) if count is None else count}


@contextlib.contextmanager
def kernel_backend(name):
    """Trace the kernels under another backend (the jnp reference)."""
    old = os.environ.get(dispatch._ENV_VAR)
    os.environ[dispatch._ENV_VAR] = name
    try:
        yield
    finally:
        if old is None:
            del os.environ[dispatch._ENV_VAR]
        else:
            os.environ[dispatch._ENV_VAR] = old


def expected_bits(state, dc):
    """The quantizer's static wire meter summed over the model's leaves."""
    q = QuantizePNorm(bits=dc.bits, block=dc.block)
    return float(sum(q.wire_bits(int(np.prod(l.shape[1:])))
                     for l in jax.tree_util.tree_leaves(state.params)))


def train_argv(mesh, layers, seq_len, steps, reduced):
    argv = ["--mesh-shape", mesh, "--layers", str(layers),
            "--seq-len", str(seq_len), "--batch-per-agent", "2",
            "--steps", str(steps), "--algorithm", "lead", "--bits", "2",
            "--log-every", str(steps + 1)]
    return argv + (["--reduced"] if reduced else [])


# ---------------------------------------------------------------------------
# train: one chip, one agent
# ---------------------------------------------------------------------------

def phase_train(layers=4, seq_len=1024, steps=5, reduced=False):
    run = train_mod.build(train_mod.parse_args(
        train_argv("1,1", layers, seq_len, steps, reduced)))
    want_bits = expected_bits(run.state, run.dc)
    times, marks = {}, {}

    def before(i, state, batch, key):
        jax.block_until_ready(state)
        marks[i] = len(_compile_secs)
        times[i] = time.perf_counter()
        return state

    def after(i, state, batch, metrics):
        jax.block_until_ready((state, metrics))
        times[i] = time.perf_counter() - times[i]
        marks[i] = sum(_compile_secs[marks[i]:])
        loss = float(jnp.mean(run.loss_fn(state.params, batch)))
        gnorm = float(metrics["grad_norm"])
        bits = float(metrics["bits_per_agent"])
        say("train", step=i + 1, loss=loss, grad_norm=gnorm,
            bits_per_agent=bits, seconds=times[i], compile_s=marks[i])
        assert np.isfinite(loss) and np.isfinite(gnorm), (loss, gnorm)
        assert abs(bits - want_bits) <= 1e-6 * want_bits, (bits, want_bits)

    train_mod.loop(run, before, after)
    n_compiled = run.step_fn._cache_size()
    assert n_compiled == 1, f"step compiled {n_compiled} times"
    warm = [times[i] for i in sorted(times)[1:]]
    say("train", compile_s=marks[0], steps_compiled=n_compiled)
    say("train", step_s_after_warmup=warm, median=float(np.median(warm)))
    say("train", wire_bits_expected=want_bits)


# ---------------------------------------------------------------------------
# engine: the flat LEAD hot path with compiled kernels
# ---------------------------------------------------------------------------

def _off(a, b, tol):
    """Elements of a that miss b by more than tol; a NaN or an inf (say,
    from a grid step that never wrote its rows) counts as a miss."""
    return ~(jnp.abs(a - b) <= tol)


def _deviating(a, b, tol):
    return int(jnp.sum(_off(a, b, tol))), a.size


def phase_engine(n=8, d=2 ** 24, steps=3, seed=0, on_chip=True):
    comp = QuantizePNorm(bits=2, block=512)
    eng = engine_for(topology.ring(n), comp, d)
    key = jax.random.PRNGKey(seed)
    x0 = jax.random.normal(key, (n, d), jnp.float32)
    state = jax.jit(eng.init)(x0, x0, key)
    del x0
    step = jax.jit(lambda s, g, k: eng.step_with_wire(s, g, k))
    g = jax.jit(lambda k: eng.blockify(jax.random.normal(k, (n, d))))(key)
    k0 = jax.random.fold_in(key, 1)
    fused = step.lower(state, g, k0).compile()
    with kernel_backend("jnp"):
        ref = jax.jit(lambda s, g, k: eng.step_with_wire(s, g, k)
                      ).lower(state, g, k0).compile()
    if on_chip:
        assert "tpu_custom_call" in fused.as_text(), "no Pallas kernel in step"
        assert "tpu_custom_call" not in ref.as_text(), "reference ran kernels"
        say("engine", n=n, d=d, rows=n * eng.nb, tpu_custom_call="present")

    bad = total = 0
    for s in range(steps):
        ks = jax.random.fold_in(key, 100 + s)
        g = jax.jit(lambda k: eng.blockify(jax.random.normal(k, (n, d))))(ks)
        new, _, bits = fused(state, g, ks)
        want, _, want_bits = ref(state, g, ks)
        scale = max(1.0, float(jnp.max(jnp.abs(want.x))))
        for f in ("x", "h", "hw", "d"):
            nb_, nt = _deviating(getattr(new, f), getattr(want, f),
                                 1e-4 * scale)
            bad, total = bad + nb_, total + nt
        assert float(bits) == float(want_bits), (float(bits), float(want_bits))
        say("engine", step=s + 1, deviating=bad, of=total,
            bits_per_agent=float(bits))
        state = new
    frac = bad / total
    say("engine", pallas_vs_jnp_deviating_frac=frac)
    assert frac < 1e-5, (bad, total)
    return frac


EMBED_ROWS = 49155 * 2048 // 512     # granite-3-2b's embedding in 512-blocks


def phase_ragged(rows=EMBED_ROWS, block=512, seed=0, backend="pallas"):
    """The kernels' partial last grid step.  LEAD's apply stage as the
    trainer runs it on granite-3-2b's embedding leaf (one agent, 196,620
    rows of 512) and the fused diff+encode kernel at the same rows, under
    ``backend`` and under the jnp reference.  fit_tile gives 256 rows a
    step, so the last step holds 12 rows; those must all agree."""
    tile = fit_tile(rows)
    tail = rows % tile
    assert tail, f"{rows} rows end in a whole tile of {tile}"
    eng = engine_of(DistConfig(algorithm="lead", bits=2, block=block), 1)
    comp = QuantizePNorm(bits=2, block=block)

    def inputs(key):
        ks = jax.random.split(key, 8)
        bufs = [jax.random.normal(k, (1, rows, block)) for k in ks[:7]]
        return bufs, jax.random.uniform(ks[7], (rows, block))

    def run(bufs, u):       # called through a fresh lambda per backend:
        x, g, h, hw, d, qh, wqh = bufs  # jit caches traces by function
        k = jnp.zeros((), jnp.int32)
        hy = eng.hypers_at(k)
        new = eng.apply_stage(eng.state_cls(x=x, h=h, hw=hw, d=d, k=k),
                              g, qh, wqh, hy)[0]
        code, scale = lead_diff_encode(x[0], g[0], d[0], h[0], u,
                                       hy["eta"], bits=comp.bits)
        return {"x": new.x[0], "d": new.d[0], "h": new.h[0],
                "hw": new.hw[0],
                "q": comp.decode_blocks({"code": code, "scale": scale})}

    args = jax.jit(inputs)(jax.random.PRNGKey(seed))
    with kernel_backend(backend):
        tested = jax.jit(lambda *a: run(*a)).lower(*args).compile()
    with kernel_backend("jnp"):
        ref = jax.jit(lambda *a: run(*a)).lower(*args).compile()
    if backend == "pallas":
        assert "tpu_custom_call" in tested.as_text(), "no Pallas kernel"
        assert "tpu_custom_call" not in ref.as_text(), "reference ran kernels"
    got, want = tested(*args), ref(*args)
    tol = 1e-4 * max(1.0, float(jnp.max(jnp.abs(want["x"]))))
    bad = total = tail_bad = 0
    for f in want:
        dev = _off(got[f], want[f], tol)
        bad, total = bad + int(jnp.sum(dev)), total + dev.size
        tail_bad += int(jnp.sum(dev[-tail:]))
    say("engine", ragged_rows=rows, tile=tile, last_block_rows=tail,
        backend=backend, deviating=bad, of=total,
        last_block_deviating=tail_bad)
    assert bad / total < 1e-5 and tail_bad == 0, (bad, total, tail_bad)
    return bad / total


def _lambda_max(A, iters=30):
    """Largest eigenvalue of A_i^T A_i over agents, by power iteration."""
    v = jnp.ones((A.shape[0], A.shape[2]), jnp.float32)
    for _ in range(iters):
        w = jnp.einsum("nmd,nm->nd", A,
                       jnp.einsum("nmd,nd->nm", A, v, precision=HIGHEST),
                       precision=HIGHEST)
        lam = jnp.linalg.norm(w, axis=1)
        v = w / lam[:, None]
    return float(jnp.max(lam))


def _x_star(prob):
    """The least-squares optimum by conjugate gradients on the normal
    equations (LinearRegression.x_star's dense LU at d = 16384 exceeds the
    TPU's scoped VMEM); returns x* and its relative residual."""
    A, lam, n = prob.A, prob.lam, prob.n

    def hess(v):
        Av = jnp.einsum("nmd,d->nm", A, v, precision=HIGHEST)
        return (2.0 * jnp.einsum("nmd,nm->d", A, Av, precision=HIGHEST)
                + 2.0 * n * lam * v)

    rhs = 2.0 * jnp.einsum("nmd,nm->d", A, prob.b, precision=HIGHEST)
    x, _ = jax.scipy.sparse.linalg.cg(hess, rhs, tol=1e-7, maxiter=500)
    return x, float(jnp.linalg.norm(hess(x) - rhs) / jnp.linalg.norm(rhs))


def phase_simulator(n=8, d=16384, m=2048, iters=300, lam=1.0, seed=0):
    """LEAD on a strongly convex least-squares problem (m * n >= d rows),
    through the engine as a user builds it, at its own matmul precision."""
    prob = LinearRegression.generate(jax.random.PRNGKey(seed),
                                     n_agents=n, m=m, d=d, lam=lam)
    x_star, resid = _x_star(prob)
    assert resid < 1e-5, resid
    L = 2.0 * _lambda_max(prob.A) + 2.0 * lam
    eta = 1.0 / L
    eng = engine_for(topology.ring(n), QuantizePNorm(bits=2, block=512),
                     d, eta=eta)
    tr = simulator.run(eng, prob, x_star, iters=iters,
                       key=jax.random.PRNGKey(seed + 1))
    fall = float(tr.dist[0] / tr.dist[-1])
    say("engine", simulator_iters=iters, n=n, d=d, m=m, eta=eta,
        x_star_residual=resid,
        dist_first=float(tr.dist[0]), dist_last=float(tr.dist[-1]),
        dist_fall=fall)
    assert np.all(np.isfinite(tr.dist)) and fall >= 1e3, fall
    return fall


# ---------------------------------------------------------------------------
# four chips: four agents, gossip over ICI, against a dense-W reference
# ---------------------------------------------------------------------------

def _checked_leaves(params, block):
    """(leaf index, layer-0 slice?) for the embedding and every layer-0
    leaf (a host reference of every full-width leaf would not fit), and the
    names of layer-0 leaves left out because their slice does not end on a
    quantization block (none at published widths)."""
    out, skipped = [], []
    for i, (path, leaf) in enumerate(
            jax.tree_util.tree_flatten_with_path(params)[0]):
        key = jax.tree_util.keystr(path)
        if key.startswith("['embed']"):
            out.append((i, False))
        elif key.startswith("['layers']"):
            if int(np.prod(leaf.shape[2:])) % block:
                skipped.append(key)
            else:
                out.append((i, True))
    return out, skipped


def _blocks(a, block):
    """(A, ...) host leaf -> (A, rows, block); the leaf is a block multiple."""
    a = np.asarray(a, np.float32).reshape(a.shape[0], -1)
    assert a.shape[1] % block == 0, a.shape
    return a.reshape(a.shape[0], -1, block)


def phase_four_chips(layers=4, seq_len=1024, steps=5, reduced=False):
    """The compile-only memory analysis puts the four-agent step at 14.85
    GiB per v5e chip at seq_len 1024 (the received payloads add ~1.5 GiB
    over one agent's 13.36 GiB)."""
    run = train_mod.build(train_mod.parse_args(
        train_argv("4,1", layers, seq_len, steps, reduced)))
    A, dc, cfg = run.n_agents, run.dc, run.cfg
    assert A == 4, A
    eng = engine_of(dc, A)
    eta, gamma, alpha = (float(eng.eta), float(eng.gamma), float(eng.alpha))
    comp = QuantizePNorm(bits=dc.bits, block=dc.block)
    W = np.asarray(topology.ring(A).W, np.float32)
    want_bits = expected_bits(run.state, dc)
    checked, skipped = _checked_leaves(run.state.params, dc.block)
    say("four_chips", checked_leaves=len(checked), skipped=skipped)
    assert reduced or not skipped, skipped
    grad_fn = jax.jit(jax.vmap(jax.grad(
        lambda p, b: tfm.loss_fn(p, cfg, b)[0])))
    cpu = jax.devices("cpu")[0]
    host_mesh = jax.sharding.Mesh(np.array([cpu]), ("host",))

    @functools.partial(jax.jit, static_argnums=6)
    def ref_leaf(kk, x, g, h, hw, d, u_rows):
        # Alg. 1 lines 4-7 on one leaf, dense W over the agent axis
        keys = jax.random.split(kk, x.shape[0])
        u = jax.vmap(lambda k: jax.random.uniform(
            k, (u_rows, dc.block), jnp.float32))(keys)[:, :x.shape[1]]
        y = x - eta * g - eta * d
        code, scale = _stochastic_quantize(y - h, u, comp.bits, comp.p)
        qh = comp.decode_blocks({"code": code, "scale": scale})
        wqh = jnp.tensordot(W, qh, axes=([1], [0]))
        yh, yhw = h + qh, hw + wqh
        d2 = d + gamma / (2 * eta) * (yh - yhw)
        return {"x": x - eta * g - eta * d2,
                "h": (1 - alpha) * h + alpha * yh,
                "hw": (1 - alpha) * hw + alpha * yhw, "d": d2}

    def fields(state):
        return {"x": state.params, **state.algo}

    pending, tally = {}, {"bad": 0, "total": 0, "scale": 1.0}

    def leaf_of(tree, i, layer0):
        leaf = jax.tree_util.tree_leaves(tree)[i]
        return leaf[:, 0] if layer0 else leaf

    def before(i, state, batch, key):
        # re-sync the checked leaves to the reference of the last step
        if pending:
            trees = fields(state)
            for f, tree in trees.items():
                leaves, treedef = jax.tree_util.tree_flatten(tree)
                for (li, layer0), ref in zip(checked, pending[f]):
                    ref = ref.reshape(leaf_of(tree, li, layer0).shape)
                    leaves[li] = jax.device_put(
                        leaves[li].at[:, 0].set(ref) if layer0 else ref,
                        leaves[li].sharding)
                trees[f] = jax.tree_util.tree_unflatten(treedef, leaves)
            state = state._replace(
                params=trees.pop("x"), algo=trees)
        # the reference step of the checked leaves, from this state
        grads = grad_fn(state.params, batch)
        host = {f: [np.asarray(jax.device_get(leaf_of(t, li, l0)))
                    for li, l0 in checked]
                for f, t in {**fields(state), "g": grads}.items()}
        del grads
        keys = np.asarray(jax.device_get(jax.random.split(
            key, len(jax.tree_util.tree_leaves(state.params)))))
        out = {f: [] for f in ("x", "h", "hw", "d")}
        with jax.set_mesh(host_mesh), jax.default_device(cpu):
            for j, (li, layer0) in enumerate(checked):
                full = jax.tree_util.tree_leaves(state.params)[li]
                u_rows = int(np.prod(full.shape[1:])) // dc.block
                r = ref_leaf(keys[li],
                             *[_blocks(host[f][j], dc.block)
                               for f in ("x", "g", "h", "hw", "d")],
                             u_rows)
                for f in out:
                    out[f].append(np.asarray(r[f]))
        pending.clear()
        pending.update(out)
        return state

    def after(i, state, batch, metrics):
        trees = fields(state)
        for f in ("x", "h", "hw", "d"):
            for (li, layer0), ref in zip(checked, pending[f]):
                got = np.asarray(jax.device_get(
                    leaf_of(trees[f], li, layer0)))
                if f == "x":
                    tally["scale"] = max(tally["scale"],
                                         float(np.max(np.abs(ref))))
                dev = ~(np.abs(got.reshape(ref.shape) - ref)
                        <= 1e-4 * tally["scale"])
                tally["bad"] += int(dev.sum())
                tally["total"] += dev.size
        bits = float(metrics["bits_per_agent"])
        assert abs(bits - want_bits) <= 1e-6 * want_bits, (bits, want_bits)
        dsum = max(float(jnp.max(jnp.abs(jnp.sum(l, 0))))
                   for l in jax.tree_util.tree_leaves(state.algo["d"]))
        dmax = max(float(jnp.max(jnp.abs(l)))
                   for l in jax.tree_util.tree_leaves(state.algo["d"]))
        xmax = max(float(jnp.max(jnp.abs(l)))
                   for l in jax.tree_util.tree_leaves(state.params))
        # each step's gamma/(2 eta) (yh - yhw) cancels over the agents up
        # to f32 roundoff of the iterate, about an ulp per agent; a W that
        # is not doubly stochastic leaves its error (>= 1e-3 for a bf16 W)
        # times the iterate instead
        dbound = (16 * (i + 1) * A * float(np.finfo(np.float32).eps)
                  * gamma / (2 * eta) * xmax)
        say("four_chips", step=i + 1, deviating=tally["bad"],
            of=tally["total"], bits_per_agent=bits,
            wire_bits_expected=want_bits, dual_sum_max=dsum,
            dual_sum_bound=dbound, d_max=dmax,
            grad_norm=float(metrics["grad_norm"]))
        assert np.isfinite(float(metrics["grad_norm"]))
        assert dsum < dbound, (dsum, dbound, dmax)

    train_mod.loop(run, before, after)
    frac = tally["bad"] / tally["total"]
    n_compiled = run.step_fn._cache_size()
    say("four_chips", dense_w_reference_deviating_frac=frac,
        steps_compiled=n_compiled)
    assert frac < 1e-5, (tally["bad"], tally["total"])
    assert n_compiled == 1, f"step compiled {n_compiled} times"
    return frac


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-agent trainer across 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = require_chip(4 if args.four_chips else None)
    say("device", **device, cache=use_compile_cache())
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    phases = ([phase_four_chips] if args.four_chips else
              [phase_train, functools.partial(phase_engine, seed=args.seed),
               functools.partial(phase_ragged, seed=args.seed),
               functools.partial(phase_simulator, seed=args.seed)])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        say("phase", name=getattr(phase, "func", phase).__name__,
            seconds=time.perf_counter() - t0)
        say("memory", **jax.devices()[0].memory_stats())
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:                               # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"chip_smoke.py: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        sys.exit(1)

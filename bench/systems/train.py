"""Training cells: the decentralized trainer exactly as launch/train builds
it, driven by the benchmark's own weights and token streams.

Set-up builds the run through ``repro.launch.train.build``, replaces its
state by the benchmark's weights (drawn from the seed on the device, in the
state's own layout and placement), and takes the first ``CHECKED_STEPS``
steps through the same jitted step the window then keeps calling.  Those
steps are read for the comparison with the plain reference; they also warm
every program the window uses.  The window is a closed loop: one step per
batch, at most two in flight, ended in ``block_until_ready``.  After the
window the iterate is copied to the host, the program's state is freed and
the reference (bench/reference) retraces the checked steps from the seed;
the iterate's distance from the reference's after those steps, against the
gradients the later steps reported, shows whether the window's steps moved
the state.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, counts
from bench.generators import lm_stream
from bench.reference import lead as ref_lead
from bench.reference import transformer as ref_tf
from bench.systems import quiet_host

CHECKED_STEPS = 3
TRACED_STEPS = 3


def _keys(key):
    """(weights, data, step) keys of a run."""
    return (jax.random.fold_in(key, 0), jax.random.fold_in(key, 1),
            jax.random.fold_in(key, 2))


def step_key(key, k):
    return jax.random.fold_in(key, k)


def train_argv(config: dict, traffic: dict) -> list:
    m, alg = config["model"], config["algorithm"]
    argv = ["--arch", config["arch"], "--mesh-shape", f"{traffic['agents']},1",
            "--layers", str(m["n_layers"]), "--seq-len",
            str(traffic["seq_len"]), "--batch-per-agent",
            str(traffic["batch_per_agent"]), "--steps", "0",
            "--algorithm", alg["name"], "--bits", str(alg["bits"]),
            "--eta", repr(alg["eta"]), "--topology", alg["topology"],
            "--optimizer", alg["optimizer"]]
    return argv + (["--reduced"] if config.get("arch_reduced") else [])


def generator(traffic: dict, vocab: int, agents: int):
    """The traffic's token streams, sizes bound (jit the result)."""
    if traffic["generator"] != "lm_stream":
        raise ValueError(f"training reads lm_stream traffic, not "
                         f"{traffic['generator']!r}")
    return functools.partial(
        lm_stream.batch, vocab=vocab, seq_len=traffic["seq_len"],
        batch_per_agent=traffic["batch_per_agent"], n_agents=agents,
        block_size=traffic["block_size"])


@contextlib.contextmanager
def stated_model(train_mod, m: dict):
    """launch/train's ``build`` reads its model from the registry, which
    leaves ``tie_embeddings`` at its default (an untied head) where the
    published model ties it; within this block ``build`` reads the
    registry's model with the head tied as the configuration states.
    check_model then holds every other size to the configuration."""
    registry = train_mod.get_config

    def get_config(name):
        return dataclasses.replace(registry(name),
                                   tie_embeddings=m["tie_embeddings"])

    train_mod.get_config = get_config
    try:
        yield
    finally:
        train_mod.get_config = registry


def check_model(run_cfg, m: dict):
    """The program must run the model the configuration states."""
    got = {"n_layers": run_cfg.n_layers, "d_model": run_cfg.d_model,
           "n_heads": run_cfg.n_heads, "kv_heads": run_cfg.kv_heads,
           "head_dim": run_cfg.head_dim, "d_ff": run_cfg.d_ff,
           "vocab": run_cfg.vocab, "rope_theta": run_cfg.rope_theta,
           "tie_embeddings": run_cfg.tie_embeddings,
           "mlp_type": run_cfg.mlp_type, "family": run_cfg.family}
    want = {k: m[k] for k in got}
    if got != want:
        raise RuntimeError(f"the program's model {got} is not the "
                           f"configuration's {want}")


class Program:
    """The system under test, built and seeded."""

    def __init__(self, config: dict, traffic: dict, seed_key,
                 dc_overrides: dict | None = None):
        from repro.dist import sharding as shr
        from repro.dist.trainer import engine_of, make_train_step
        from repro.launch import train as train_mod
        from jax.sharding import NamedSharding

        self.config, self.traffic = config, traffic
        self.m, self.alg = config["model"], config["algorithm"]
        with stated_model(train_mod, self.m):
            run = train_mod.build(train_mod.parse_args(train_argv(config,
                                                                  traffic)))
        check_model(run.cfg, self.m)
        if run.dc.block != self.alg["block"] or run.dc.bits != self.alg["bits"]:
            raise RuntimeError(f"wire {run.dc.bits} bits / block "
                               f"{run.dc.block} is not the configuration's")
        self.run, self.A = run, run.n_agents
        if dc_overrides:
            # a control: the same trainer with another of its own paths
            run.dc = dataclasses.replace(run.dc, **dc_overrides)
            with jax.set_mesh(run.mesh):
                run.step_fn = jax.jit(
                    make_train_step(run.cfg, run.mesh, run.prof, run.dc),
                    donate_argnums=0)
        self.eng = engine_of(run.dc, self.A)
        hy = {f: float(getattr(self.eng, f)) for f in ("eta", "gamma",
                                                        "alpha")}
        if hy != {f: float(self.alg[f]) for f in hy}:
            raise RuntimeError(f"the trainer's LEAD hypers {hy} are not the "
                               "configuration's")
        self.state_dtype = jnp.dtype(run.dc.state_dtype)
        self.shardings = jax.tree_util.tree_map(lambda a: a.sharding,
                                                run.state)
        want = jax.tree_util.tree_map(
            lambda s: (self.A,) + s.shape, ref_tf.weights_shapes(self.m))
        got = jax.tree_util.tree_map(lambda a: a.shape, run.state.params)
        if (jax.tree_util.tree_structure(want)
                != jax.tree_util.tree_structure(got) or want != got):
            raise RuntimeError("the program's parameter tree is not the "
                               "reference's layout")
        self.wkey, self.dkey, self.skey = _keys(seed_key)
        self.gen = jax.jit(
            generator(traffic, self.m["vocab"], self.A),
            out_shardings=NamedSharding(run.mesh,
                                        shr.train_batch_spec(run.prof)))
        with jax.set_mesh(run.mesh):
            self.state = jax.jit(self._seeded, donate_argnums=0,
                                 out_shardings=self.shardings)(
                run.state, self.wkey)
        run.state = None

    def _seeded(self, state, wkey):
        """The trainer's consensus start from the benchmark's weights: every
        agent holds the same replica, and each further state field is a
        copy of it or zeros, as the engine declares."""
        sd = self.state_dtype
        params = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l.astype(sd)[None],
                                       (self.A,) + l.shape),
            ref_tf.weights(wkey, self.m))
        algo = {f: (params if kind == "copy" else
                    jax.tree_util.tree_map(jnp.zeros_like, params))
                for f, kind in self.eng.consensus_init.items()}
        return state._replace(params=params, algo=algo,
                              step=jnp.zeros((), jnp.int32))

    @property
    def tokens_per_step(self) -> int:
        t = self.traffic
        return self.A * t["batch_per_agent"] * t["seq_len"]

    def batch(self, k):
        return self.gen(self.dkey, k)

    def step(self, k):
        """Step k through the trainer's jitted step; returns its metrics."""
        with jax.set_mesh(self.run.mesh):
            self.state, metrics = self.run.step_fn(
                self.state, self.batch(k), step_key(self.skey, k))
        return metrics

    def loss(self, k):
        """The program's own per-agent loss of its parameters on batch k."""
        with jax.set_mesh(self.run.mesh):
            return self.run.loss_fn(self.state.params, self.batch(k))

    def free(self):
        for a in jax.tree_util.tree_leaves(self.state):
            a.delete()
        self.state = None
        self.run = None


@functools.partial(jax.jit, static_argnames=("m", "eta"))
def _first_grad_norms(params, d, wkey, *, m, eta):
    """Per (leaf, agent): ||(x0 - x1) / eta - d1||, the gradient the step's
    optimizer was handed, from the state after step 1."""
    x0 = ref_tf.weights(wkey, m)

    def one(x1, dd, x0l):
        g = (x0l.astype(jnp.float32)[None] - x1.astype(jnp.float32)) / eta \
            - dd.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(jnp.square(g.reshape(g.shape[0], -1)), 1))

    return jnp.stack(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(one, params, d, x0)))


@functools.partial(jax.jit, static_argnames=("m", "copy"))
def _change_norms(field, wkey, *, m, copy: bool):
    """Per (leaf, agent): ||f_now - f_0|| where f_0 is the start's replica
    (copy) or zeros."""
    x0 = ref_tf.weights(wkey, m)

    def one(f, x0l):
        f0 = x0l[None] if copy else jnp.zeros_like(x0l)[None]
        diff = f.astype(jnp.float32) - f0
        return jnp.sqrt(jnp.sum(jnp.square(diff.reshape(diff.shape[0], -1)),
                                1))

    return jnp.stack(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(one, field, x0)))


def program_readings(prog: Program) -> dict:
    """Step the program through the checked steps; the numbers the
    reference is compared on."""
    m, eta = prog.m, float(prog.alg["eta"])
    out = {"loss": [], "grad_norm": [], "bits": []}
    mhash = ref_tf.Frozen(m)
    for k in range(CHECKED_STEPS):
        out["loss"].append(np.asarray(prog.loss(k), np.float64))
        metrics = prog.step(k)
        out["grad_norm"].append(float(metrics["grad_norm"]))
        out["bits"].append(float(metrics["bits_per_agent"]))
        if k == 0:
            out["first_grad"] = np.asarray(_first_grad_norms(
                prog.state.params, prog.state.algo["d"], prog.wkey, m=mhash,
                eta=eta))
    fields = {"x": (prog.state.params, True)}
    for f, kind in prog.eng.consensus_init.items():
        fields[f] = (prog.state.algo[f], kind == "copy")
    out["change"] = {f: np.asarray(_change_norms(v, prog.wkey, m=mhash,
                                                 copy=c))
                     for f, (v, c) in fields.items()}
    out["loss"] = np.stack(out["loss"])
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "agent"))
def _ref_grad(x, tokens, labels, *, m, agent):
    """Agent ``agent``'s loss and gradient, one sequence at a time (the
    batch mean of per-row means: every row has the same length)."""
    p = jax.tree_util.tree_map(lambda l: l[agent], x)
    B = tokens.shape[1]

    def row(carry, tl):
        loss, g = jax.value_and_grad(ref_tf.loss)(p, m, tl[0][None],
                                                  tl[1][None])
        return (carry[0] + loss / B,
                jax.tree_util.tree_map(lambda a, b: a + b / B, carry[1], g)
                ), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, p))
    (loss, g), _ = jax.lax.scan(row, zero, (tokens[agent], labels[agent]))
    return loss, g


@functools.partial(jax.jit, static_argnames=("bits", "block", "eta", "gamma",
                                             "alpha"), donate_argnums=(0, 2, 3,
                                                                       4))
def _ref_leaf_step(x, g, h, hw, d, key, W, *, bits, block, eta, gamma,
                   alpha):
    """LEAD on one stacked leaf (A, ...) in the quantizer's blocks."""
    A, shape = x.shape[0], x.shape
    n = int(np.prod(shape[1:]))
    rows = counts.blocks(n, block)

    def blk(a):
        a = a.reshape(A, -1)
        return jnp.pad(a, ((0, 0), (0, rows * block - n))).reshape(A, rows,
                                                                   block)

    u = ref_lead.dither(key, A, rows, block)
    new = ref_lead.step(blk(x), blk(g), blk(h), blk(hw), blk(d), u, W,
                        eta=eta, gamma=gamma, alpha=alpha, bits=bits)
    return tuple(a.reshape(A, -1)[:, :n].reshape(shape) for a in new)


def homes(sizes, n_devices: int) -> list:
    """Each leaf's device index: the largest leaves first, each to the
    device that holds the fewest elements so far."""
    load, out = [0] * n_devices, [0] * len(sizes)
    for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
        out[i] = min(range(n_devices), key=lambda j: load[j])
        load[out[i]] += sizes[i]
    return out


def _blocks(a, block: int):
    """(A, ...) -> (A, rows, block): each agent's leaf, flattened, in the
    quantizer's blocks, the last one padded with zeros."""
    n = int(np.prod(a.shape[1:]))
    rows = counts.blocks(n, block)
    return jnp.pad(a.reshape(a.shape[0], -1),
                   ((0, 0), (0, rows * block - n))).reshape(-1, rows, block)


def _flat(a, n: int):
    """(A, rows, block) -> (A, n): each agent's n elements, unpadded."""
    return a.reshape(a.shape[0], -1)[:, :n]


def reference_readings(config: dict, traffic: dict, seed_key) -> dict:
    """The checked steps retraced by the plain reference from the seed.

    A leaf's four LEAD fields (x, h, hw, d), each stacked over the agents in
    the quantizer's blocks (A, rows, block), lie on the leaf's home device;
    the leaves spread over the devices by size, so that four agents' state
    (22 GB at granite-3-2b widths) need not fit one chip.  Agent a's
    gradient is taken on device a % n from its own slice of every leaf;
    each leaf's LEAD step runs on its home, where its mix sees every agent.
    In blocks, the step relayouts no field (the embedding's rows, 49155,
    are no multiple of the TPU's tile of 8).  On one device every leaf lies
    there."""
    devices = jax.devices()
    m = ref_tf.Frozen(config["model"])
    alg = config["algorithm"]
    if alg["name"] != "lead" or alg["topology"] != "ring":
        raise NotImplementedError("the reference runs LEAD on a ring")
    A, block = traffic["agents"], alg["block"]
    W = np.asarray(ref_lead.ring(A), np.float32)
    eta, gamma, alpha = (float(alg[k]) for k in ("eta", "gamma", "alpha"))
    wkey, dkey, skey = _keys(seed_key)
    gen = jax.jit(generator(traffic, m["vocab"], A))
    stacked = jax.jit(lambda l: _blocks(
        jnp.broadcast_to(l[None], (A,) + l.shape), block))
    unblocks = jax.jit(lambda a, shape: _flat(a, int(np.prod(shape))).reshape(
        (a.shape[0],) + shape), static_argnums=1)
    w, treedef = jax.tree_util.tree_flatten(
        jax.jit(ref_tf.weights, static_argnums=1)(wkey, m))
    shapes = [l.shape for l in w]
    sizes = [l.size for l in w]
    home = [devices[j] for j in homes(sizes, len(devices))]
    Ws = {dev: jax.device_put(W, dev) for dev in set(home)}
    # LEAD's consensus start: H = X0, H_w = W X0 = X0, D = 0
    fields = []                                  # per leaf: [x, h, hw, d]
    for l, dev in zip(w, home):
        x = stacked(jax.device_put(l, dev))
        fields.append([x, jnp.copy(x), jnp.copy(x), jnp.zeros_like(x)])
    del w, x
    out = {"loss": [], "grad_norm": [], "bits": []}
    wire = sum(counts.quantizer_wire_bits(n, alg["bits"], block)
               for n in sizes)
    for k in range(CHECKED_STEPS):
        b = gen(dkey, k)
        losses, grads = [], []
        for a in range(A):
            dev = devices[a % len(devices)]

            def part(leaf):
                return jax.device_put(leaf[a:a + 1], dev)

            xa = jax.tree_util.tree_unflatten(treedef, [
                unblocks(part(f[0]), shape)
                for f, shape in zip(fields, shapes)])
            loss, g = _ref_grad(xa, part(b["tokens"]), part(b["labels"]),
                                m=m, agent=0)
            losses.append(loss)
            grads.append([_blocks(l[None], block)
                          for l in jax.tree_util.tree_leaves(g)])
        del xa, g
        gl = [jnp.concatenate([jax.device_put(ga[i], dev) for ga in grads])
              for i, dev in enumerate(home)]
        del grads
        per = np.stack([np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            _flat(l, n)), 1))) for l, n in zip(gl, sizes)])
        out["loss"].append(np.asarray([float(v) for v in losses]))
        out["grad_norm"].append(float(np.sqrt(np.sum(per.astype(np.float64)
                                                      ** 2))))
        out["bits"].append(float(wire))
        if k == 0:
            out["first_grad"] = per
        keys = jax.random.split(step_key(skey, k), len(fields))
        for i, dev in enumerate(home):
            x, h, hw, d = fields[i]
            fields[i] = list(_ref_leaf_step(
                x, gl[i], h, hw, d, jax.device_put(keys[i], dev), Ws[dev],
                bits=alg["bits"], block=block, eta=eta, gamma=gamma,
                alpha=alpha))
        del gl, x, h, hw, d
    w = jax.tree_util.tree_leaves(
        jax.jit(ref_tf.weights, static_argnums=1)(wkey, m))
    x0 = [stacked(jax.device_put(l, dev)) for l, dev in zip(w, home)]
    del w

    def norms(j, zero=False):
        return np.stack([np.asarray(jnp.sqrt(jnp.sum(jnp.square(_flat(
            f[j] - (jnp.zeros_like(b) if zero else b), n)), 1)))
            for f, b, n in zip(fields, x0, sizes)])

    out["change"] = {"x": norms(0), "h": norms(1), "hw": norms(2),
                     "d": norms(3, zero=True)}
    del x0
    for f in fields:
        del f[1:]
    out["loss"] = np.stack(out["loss"])
    out["x"] = jax.tree_util.tree_unflatten(treedef, [
        unblocks(f[0], shape) for f, shape in zip(fields, shapes)])
    return out


@jax.jit
def _squared_distance(a, b):
    return jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))


def distance(host_tree, device_tree) -> float:
    """||a - b|| over every leaf, one leaf at a time on the device that
    holds its ``b``."""
    total = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(host_tree),
                    jax.tree_util.tree_leaves(device_tree)):
        total += float(_squared_distance(
            jax.device_put(a, next(iter(b.devices()))), b))
    return float(np.sqrt(total))


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------

def measure(cell, seed_key, seconds: float, tracer=None,
            program=None) -> dict:
    """Set-up, the window, the traced steps (when ``tracer``), the memory
    peak and the comparison with the reference.  ``program`` stands in for
    the trainer (the control)."""
    config, traffic = cell.config, cell.traffic
    prog = (program or Program)(config, traffic, seed_key)
    readings = program_readings(prog)
    jax.block_until_ready(prog.state)
    want_bits = readings["bits"][0]
    t_setup_end = time.perf_counter()
    clock = cell.compile_clock
    compiles_before = clock.count()

    # the window: a closed loop, two steps in flight at most
    k = CHECKED_STEPS
    attempted = failed = 0
    pending = None
    grad_norms = []              # of every step after the checked ones
    with quiet_host():
        t0 = time.perf_counter()
        while True:
            metrics = prog.step(k)
            k += 1
            attempted += 1
            if pending is not None:
                failed += _read_step(pending, want_bits, grad_norms)
            pending = metrics
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready(prog.state)
        window_s = time.perf_counter() - t0
    failed += _read_step(pending, want_bits, grad_norms)
    compiles_in_window = clock.count() - compiles_before
    result = {
        "setup_end": t_setup_end,
        "window_s": window_s, "steps": attempted,
        "attempted": attempted, "failed": failed,
        "end_to_end": {"train_tokens_per_s":
                       attempted * prog.tokens_per_step / window_s},
        "compiles_in_window": compiles_in_window,
    }
    if tracer is not None:
        traced_metrics = []

        def traced():
            for _ in range(TRACED_STEPS):
                with jax.profiler.TraceAnnotation("step"):
                    traced_metrics.append(prog.step(k + _))
            jax.block_until_ready(prog.state)
        result["trace"] = tracer(traced)
        result["traced_steps"] = TRACED_STEPS
        for metrics in traced_metrics:
            _read_step(metrics, want_bits, grad_norms)
    result["memory_peak_bytes"] = max(
        (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for dev in jax.devices()[:cell.chips])
    result["context"] = {
        "tokens_per_step": prog.tokens_per_step,
        "flops_per_token": counts.train_flops_per_token(
            config["model"], traffic["seq_len"]),
        "lead_update_bytes_per_step_per_chip": sum(
            counts.lead_update_bytes(
                counts.blocks(n, config["algorithm"]["block"]),
                config["algorithm"]["block"])
            for n in counts.leaf_sizes(config["model"])),
        "lead_update_calls_per_step_per_chip": len(
            counts.leaf_sizes(config["model"])),
        # the metered payload one agent sends each neighbour, times the
        # neighbours it sends to (ring(4): 2; a ring of one: none)
        "metered_wire_bytes_per_step_per_chip": want_bits / 8 * int(
            np.count_nonzero(ref_lead.ring(prog.A)[0]) - 1),
    }
    x_end = jax.device_get(prog.state.params)
    prog.free()
    t_ref = time.perf_counter()
    ref = reference_readings(config, traffic, seed_key)
    moved = distance(x_end, ref.pop("x"))
    del x_end
    result["values"] = dict(
        compare.train_values(readings, ref),
        window_stall=compare.window_stall(float(config["algorithm"]["eta"]),
                                          grad_norms, moved))
    result["checks"] = compare.checks(result["values"], cell.limits) + [
        ("compiles_in_window", float(compiles_in_window), 0.0)]
    result["phases"] = {"window": window_s,
                        "after_window": t_ref - t0 - window_s,
                        "reference": time.perf_counter() - t_ref}
    return result


def _read_step(metrics, want_bits, grad_norms: list) -> int:
    """1 when a step's gradient norm is not finite or its wire bits are not
    the checked steps'; its gradient norm goes to ``grad_norms``."""
    g, bits = float(metrics["grad_norm"]), float(metrics["bits_per_agent"])
    grad_norms.append(g)
    return int(not np.isfinite(g) or bits != want_bits)

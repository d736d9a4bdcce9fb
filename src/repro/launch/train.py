import os
import sys

# --devices N must take effect before jax initializes
if "--devices" in sys.argv:
    _n = sys.argv[sys.argv.index("--devices") + 1]
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

"""End-to-end decentralized training driver.

Examples (CPU):
    # 8 virtual devices, 4 agents x TP-2, tiny model, 50 steps:
    PYTHONPATH=src python -m repro.launch.train --devices 8 \
        --mesh-shape 4,2 --arch granite-3-2b --reduced --steps 50

    # one TPU chip, one agent: published widths, depth cut to 4 layers
    python -m repro.launch.train --mesh-shape 1,1 --layers 4 \
        --seq-len 1024 --steps 5

    # production launch (real TPU pod, 256 chips):
    python -m repro.launch.train --arch granite-3-2b --production \
        --steps 1000 --algorithm lead --bits 2

``build`` sets a run up (mesh, config, state, the jitted step) and ``loop``
drives it; ``main`` is the two in sequence, and chip_smoke.py calls the same
pair with hooks that check every step.
"""
import argparse
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt
from repro.configs.registry import get_config
from repro.core import topology
from repro.core.engines import ENGINES, describe
from repro.data.synthetic import LMStreamConfig, lm_batch, stub_memory
from repro.dist import sharding as shr
from repro.dist.trainer import (DistConfig, engine_of, init_train_state,
                                make_train_step, n_agents_of,
                                state_shardings)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tfm
from repro.optim.optimizers import make_optimizer
from repro.utils.compile_cache import use_compile_cache


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument("--mesh-shape", default=None,
                    help="e.g. 4,2 (data,model) or 2,2,2 (pod,data,model)")
    ap.add_argument("--production", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers; widths stay as "
                         "published (or as --reduced made them)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-agent", type=int, default=2)
    ap.add_argument("--algorithm", default="lead",
                    choices=sorted(set(ENGINES)) + ["allreduce"],
                    help="any core/engines registry algorithm, or the "
                         "centralized allreduce reference")
    ap.add_argument("--topology", default="ring",
                    choices=sorted(topology.TOPOLOGIES),
                    help="communication graph over the agents; the gossip "
                         "ppermute schedule is derived from its neighbor "
                         "structure (core/topology.py)")
    ap.add_argument("--bits", type=int, default=2)
    ap.add_argument("--eta", type=float, default=0.03)
    ap.add_argument("--optimizer", default="sgd",
                    choices=["sgd", "momentum", "adam"])
    ap.add_argument("--heterogeneous", action="store_true", default=True)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


@dataclasses.dataclass
class Run:
    """A built training run: what ``loop`` drives and its hooks read."""
    args: argparse.Namespace
    cfg: Any
    mesh: Any
    prof: Any
    dc: DistConfig
    n_agents: int
    key: jax.Array
    state: Any
    start: int
    step_fn: Callable                    # jitted; donates the state
    loss_fn: Callable                    # jitted per-agent loss
    get_batch: Callable                  # step index -> sharded batch


def build(args: argparse.Namespace) -> Run:
    if args.production:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        shape = tuple(int(x) for x in (args.mesh_shape or "4,2").split(","))
        axes = ("pod", "data", "model")[-len(shape):]
        mesh = jax.make_mesh(shape, axes,
                             axis_types=(AxisType.Auto,) * len(shape))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    prof = shr.make_profile(cfg, mesh.axis_names)
    shr.set_mesh_for_rules(mesh)
    # eta from the CLI; every other hyper falls through to the resolved
    # engine's paper defaults (gamma/alpha for LEAD, gamma for the
    # compressed baselines, nothing extra for the exact ones)
    dc = DistConfig(algorithm=args.algorithm, bits=args.bits,
                    topology=args.topology, hyper={"eta": args.eta},
                    optimizer=make_optimizer(args.optimizer))
    A = n_agents_of(mesh, prof)
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} | "
          f"{A} agents | {cfg.name} ({cfg.n_layers} layers) | "
          f"{cfg.param_count()/1e6:.1f}M params per agent | "
          f"algorithm={args.algorithm}")
    # the registry path this run actually resolved (see core.engines.describe
    # — tests/test_docs.py pins the docs' engine matrix to the same registry)
    eng = engine_of(dc, A)
    if eng is None:
        print("registry: algorithm=allreduce (centralized SGD reference, "
              "pmean over agents — not a decentralized engine)")
    else:
        print(f"registry: {describe(eng)} "
              f"(ppermute rounds over mesh axes {prof.agent_axes})")

    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    shardings = state_shardings(cfg, mesh, prof, state_sds)
    with jax.set_mesh(mesh):
        state = jax.jit(lambda k: init_train_state(cfg, mesh, prof, dc, k),
                        out_shardings=shardings)(key)
        start = 0
        if args.ckpt_dir:
            restored, ck_step = ckpt.restore(args.ckpt_dir, state_sds)
            if restored is not None:
                state = jax.device_put(restored, shardings)
                start = ck_step
                print(f"restored step {start}")

        # the old state is dead once the step returns: donating it lets the
        # new state reuse its buffers (x, h, hw, d of a full-width agent
        # do not fit a chip twice).  The state leaves the step with the
        # layout it came in with, so the next call hits the same executable.
        step = make_train_step(cfg, mesh, prof, dc)
        if step.layout is not None:
            print(f"leaf layout: {step.layout['tiled_leaves']} of "
                  f"{step.layout['tiled_leaves'] + step.layout['padded_leaves']}"
                  f" leaves tiled, {step.layout['padded_elements']} elements "
                  "on the padded path")
        step_fn = jax.jit(step, donate_argnums=0,
                          out_shardings=(shardings, NamedSharding(mesh, P())))
        loss_fn = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    ds = LMStreamConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                        batch_per_agent=args.batch_per_agent, n_agents=A,
                        heterogeneous=args.heterogeneous)
    bspec = NamedSharding(mesh, shr.train_batch_spec(prof))

    def get_batch(i):
        b = lm_batch(ds, i)
        if cfg.family in ("vlm", "audio"):
            b["memory"] = stub_memory(cfg.family,
                                      (A, args.batch_per_agent), cfg)
        return jax.device_put(b, bspec)

    return Run(args=args, cfg=cfg, mesh=mesh, prof=prof, dc=dc, n_agents=A,
               key=key, state=state, start=start, step_fn=step_fn,
               loss_fn=loss_fn, get_batch=get_batch)


def loop(run: Run, before_step: Optional[Callable] = None,
         after_step: Optional[Callable] = None):
    """Take ``run.args.steps`` steps from ``run.state``; returns the final
    state (also left in ``run.state``).

    ``before_step(i, state, batch, key) -> state`` may replace the state a
    step starts from; ``after_step(i, state, batch, metrics)`` sees what the
    step returned.  The step donates its input state, so a hook keeps what
    it needs of a state before the step that consumes it.

    Each step is a profiler step span ``train`` (step_num i) and its batch
    fetch a span ``train.get_batch``: under ``jax.profiler.trace`` they lie
    on the device ops' clock, so an idle gap on the chip can be put down to
    the host phase that caused it.  Without a profiler they cost nothing."""
    args = run.args
    state, start = run.state, run.start
    with jax.set_mesh(run.mesh):
        t0 = time.time()
        for i in range(start, start + args.steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=i):
                with jax.profiler.TraceAnnotation("train.get_batch"):
                    batch = run.get_batch(i)
                key_i = jax.random.fold_in(run.key, i)
                if before_step is not None:
                    state = before_step(i, state, batch, key_i)
                state, metrics = run.step_fn(state, batch, key_i)
                if after_step is not None:
                    after_step(i, state, batch, metrics)
                if (i + 1) % args.log_every == 0 or i == start:
                    losses = run.loss_fn(state.params, batch)
                    print(f"step {i+1:5d} | "
                          f"loss {float(jnp.mean(losses)):.4f} | "
                          f"grad_norm {float(metrics['grad_norm']):.3f} | "
                          f"{(time.time()-t0)/(i-start+1):.2f}s/step",
                          flush=True)
                if args.ckpt_dir and (i + 1) % 100 == 0:
                    ckpt.save(args.ckpt_dir, i + 1, jax.device_get(state))
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, start + args.steps, jax.device_get(state))
    run.state = state
    return state


def main(argv=None):
    use_compile_cache()
    loop(build(parse_args(argv)))
    print("done.")


if __name__ == "__main__":
    main()

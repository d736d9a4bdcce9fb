import os
os.environ["XLA_FLAGS"] = os.environ.get("DRYRUN_XLA_FLAGS",
    "--xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices and extract the roofline terms.

Usage:
    python -m repro.launch.dryrun --arch granite-3-2b --shape train_4k \
        --mesh single --out reports/dryrun
    python -m repro.launch.dryrun --all [--mesh both]

Per combination this records (reports/dryrun/<arch>__<shape>__<mesh>.json):
    flops            HLO FLOPs per device          (compiled.cost_analysis)
    hbm_bytes        HLO bytes accessed per device
    peak_memory      bytes per device              (compiled.memory_analysis)
    collectives      per-op-type byte totals parsed from the partitioned HLO
    roofline         the three §Roofline terms in seconds + dominant term
"""
import argparse
import dataclasses
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import get_config, list_archs
from repro.data.synthetic import LMStreamConfig
from repro.dist import serve as serve_mod
from repro.dist import sharding as shard_rules
from repro.dist.trainer import (DistConfig, TrainState, init_train_state,
                                make_train_step, state_shardings)
from repro.launch.mesh import make_production_mesh
from repro.utils import roofline


def build_train_lowering(arch: str, mesh, algorithm: str = "lead",
                         shape_name: str = "train_4k", cfg_override=None,
                         dc_override=None):
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    prof = shard_rules.make_profile(cfg, mesh.axis_names)
    shard_rules.set_mesh_for_rules(mesh)
    dc = dc_override if dc_override is not None else DistConfig(algorithm=algorithm)

    from repro.dist.trainer import n_agents_of
    A = n_agents_of(mesh, prof)
    B_local = shape.global_batch // max(A, 1)
    assert B_local >= 1, f"{arch}: global_batch {shape.global_batch} < {A} agents"

    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(
        lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    st_shard = state_shardings(cfg, mesh, prof, state_sds)

    batch_sds = {
        "tokens": jax.ShapeDtypeStruct((A, B_local, shape.seq_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((A, B_local, shape.seq_len), jnp.int32),
    }
    bspec = shard_rules.train_batch_spec(prof)
    bshard = {"tokens": NamedSharding(mesh, bspec),
              "labels": NamedSharding(mesh, bspec)}
    if cfg.family in ("vlm", "audio"):
        M = cfg.vis_tokens if cfg.family == "vlm" else cfg.n_audio_frames
        batch_sds["memory"] = jax.ShapeDtypeStruct(
            (A, B_local, M, cfg.d_model), jnp.bfloat16)
        bshard["memory"] = NamedSharding(mesh, shard_rules.train_batch_spec(prof, ndim=4))

    step = make_train_step(cfg, mesh, prof, dc)
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    jitted = jax.jit(step, in_shardings=(st_shard, bshard, None))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(state_sds, batch_sds, key_sds)
    return lowered, cfg


def build_serve_lowering(arch: str, mesh, shape_name: str, cfg_override=None):
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    prof = shard_rules.make_profile(cfg, mesh.axis_names)
    shard_rules.set_mesh_for_rules(mesh)

    if shape.kind == "prefill":
        fn, sds, shardings, cfg2 = serve_mod.make_prefill(cfg, mesh, prof, shape)
        order = ["params", "tokens"] + (["memory"] if "memory" in sds else [])
    else:
        fn, sds, shardings, cfg2 = serve_mod.make_decode(cfg, mesh, prof, shape)
        order = ["params", "token", "cache"]
    jitted = jax.jit(fn, in_shardings=tuple(shardings[k] for k in order))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*(sds[k] for k in order))
    return lowered, cfg2


def run_one(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
            algorithm: str = "lead", compile_too: bool = True):
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "train":
        lowered, cfg = build_train_lowering(arch, mesh, algorithm, shape_name)
    else:
        lowered, cfg = build_serve_lowering(arch, mesh, shape_name)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "algorithm": algorithm if shape.kind == "train" else "serve",
        "n_devices": mesh.devices.size,
        "lower_s": round(time.time() - t0, 1),
    }
    if compile_too:
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)
        costs = None
        period = cfg.scan_period()
        used_scan = (shape.kind == "train" and period and cfg.n_layers > period
                     and not cfg.cross_attn_every and not cfg.encoder_layers)
        if used_scan:
            # XLA cost_analysis counts a scan body once: recover true totals
            # by exact depth extrapolation over two unrolled shallow models.
            c = []
            for n_l in (period, 2 * period):
                sub = dataclasses.replace(cfg, n_layers=n_l, scan_layers=False)
                low_s, _ = build_train_lowering(arch, mesh, algorithm,
                                                shape_name, cfg_override=sub)
                c.append(roofline.extract_costs(low_s.compile()))
            costs = roofline.extrapolate_costs(c[0], c[1],
                                               cfg.n_layers // period)
        rec.update(roofline.analyze(compiled, cfg, shape, mesh, costs=costs))
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}" + \
        (f"__{algorithm}" if shape.kind == "train" and algorithm != "lead" else "")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--algorithm", default="lead")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--no-compile", action="store_true")
    args = ap.parse_args()

    combos = []
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        for arch in list_archs():
            for shape in INPUT_SHAPES:
                for m in meshes:
                    combos.append((arch, shape, m))
    else:
        combos = [(args.arch, args.shape, m) for m in meshes]

    failures = 0
    for arch, shape, m in combos:
        try:
            rec = run_one(arch, shape, m, args.out, args.algorithm,
                          compile_too=not args.no_compile)
            dom = rec.get("roofline", {}).get("dominant", "?")
            print(f"OK   {arch:24s} {shape:12s} {m:6s} "
                  f"lower={rec['lower_s']}s compile={rec.get('compile_s','-')}s "
                  f"dominant={dom}", flush=True)
        except Exception as e:
            failures += 1
            print(f"FAIL {arch:24s} {shape:12s} {m:6s} "
                  f"{type(e).__name__}: {str(e)[:200]}", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

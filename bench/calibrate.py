"""The readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds ...] [--fault-seeds ...] [--seconds 3] [--out f]

In one process, for each seed, a whole run of the cell as bench/run.py makes
it (set-up, a window of ``--seconds``, the reference), once with the
program as it is ("sound"), once with each control in its place, and once
with each fault the cell can have planted in the program.  The controls:
for a training cell the trainer's own lower-precision paths, bf16 state and
compute ("control") and bf16 compute with f32 state ("control_compute");
for an engine cell the plain reference computed in bf16.  The faults: for a
training cell half of the batch left out, the iterate left unchanged by
every step after the checked ones (a state left unchanged from the first
step reads 1 by the measure and needs no run) and, where agents gossip,
the exchange between them cut: every link dropped, so each agent mixes its
own payload alone.  Prints one JSON line
per run and a summary: per number, the largest sound reading and the
smallest control and fault readings.
"""
import argparse
import functools
import gc
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

BF16_TRAIN = {"compute_dtype": "bfloat16", "state_dtype": "bfloat16"}
BF16_COMPUTE = {"compute_dtype": "bfloat16"}


def programs(system_name: str, system, agents: int = 1):
    """{kind: the program to run in the cell's place (None: as it is)};
    the kinds that start with "control" are controls, the rest faults."""
    import jax
    import jax.numpy as jnp
    if system_name == "train":
        from repro.core.faults import FaultModel
        class HalfBatch(system.Program):
            """Half of each batch left out: the mean over the rest."""

            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                real = self.run.step_fn
                self.run.step_fn = lambda s, b, key: real(
                    s, {n: v[:, : v.shape[1] // 2] for n, v in b.items()},
                    key)

        class StillInWindow(system.Program):
            """The checked steps sound; every later step returns the
            iterate it was given (h, hw and d still move)."""

            def __init__(self, *a, **k):
                super().__init__(*a, **k)
                real, calls = self.run.step_fn, []

                def step_fn(s, b, key):
                    calls.append(None)
                    if len(calls) <= system.CHECKED_STEPS:
                        return real(s, b, key)
                    x = jax.tree_util.tree_map(jnp.copy, s.params)
                    new, metrics = real(s, b, key)
                    return new._replace(params=x), metrics

                self.run.step_fn = step_fn

        out = {"control": functools.partial(system.Program,
                                            dc_overrides=BF16_TRAIN),
               "control_compute": functools.partial(
                   system.Program, dc_overrides=BF16_COMPUTE),
               "half_batch": HalfBatch, "still_in_window": StillInWindow}
        if agents > 1:
            # the trainer's own link faults, every link down every step
            out["gossip_cut"] = functools.partial(
                system.Program,
                dc_overrides={"faults": FaultModel(link_drop=1.0)})
        return out
    return {"control": functools.partial(system.Reference,
                                         dtype=jnp.bfloat16)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import importlib
    import jax
    from bench import run as bench_run, seeds
    cell = bench_run.Cell(bench_run._json(ROOT / "BENCHMARK.json"),
                          args.workload)
    device = bench_run.require_chip(cell.chips)
    bench_run.use_compile_cache(jax)
    cell.compile_clock = clock = bench_run.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    name = cell.config["system"]
    system = importlib.import_module(f"bench.systems.{name}")
    alts = programs(name, system, cell.traffic.get("agents", 1))

    controls = [k for k in alts if k.startswith("control")]
    plan = [("sound", s) for s in _ints(args.seeds)]
    plan += [(k, s) for s in _ints(args.control_seeds) for k in controls]
    plan += [(k, s) for s in _ints(args.fault_seeds) for k in alts
             if k not in controls]
    rows = []
    for kind, seed in plan:
        t0 = time.perf_counter()
        row = {"kind": kind, "seed": seed}
        try:
            res = system.measure(cell, seeds.seed_key(seed), args.seconds,
                                 None, program=alts.get(kind))
            row.update(values=res["values"], end_to_end=res["end_to_end"],
                       failed=res["failed"], attempted=res["attempted"],
                       memory_peak_bytes=res["memory_peak_bytes"])
        except Exception as e:                           # noqa: BLE001
            traceback.print_exc()
            row["error"] = f"{type(e).__name__}: {e}"[:500]
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        gc.collect()
    summary = {"workload": args.workload, "device": device}
    for kind in ("sound", *alts):
        vals = [r["values"] for r in rows if r["kind"] == kind
                and "values" in r]
        pick = max if kind == "sound" else min
        if vals:
            summary[kind] = {k: pick(v[k] for v in vals) for k in vals[0]}
        summary[kind + "_errors"] = sum(1 for r in rows
                                        if r["kind"] == kind and
                                        "error" in r)
    print(json.dumps(summary), flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(
            json.dumps({"rows": rows, "summary": summary}, indent=1))


def _ints(s: str) -> list:
    return [int(x) for x in s.split(",") if x.strip()]


if __name__ == "__main__":
    main()

"""The benchmark of this repository.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: BENCHMARK.json names its
configuration, traffic and chips; bench/configs/<config>.json holds the
configuration (its ``system`` picks bench/systems/<system>.py), the
traffic's file bench/traffic/<traffic>.json, the cell's limits
bench/cells/<cell>.json, and each per-layer metric is read by
bench/metrics/<metric>.py.  A run needs a TPU: without one, or when the
kernels would not run as compiled Pallas, it exits non-zero and prints no
result.  With ``--trace 0`` the result line holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of a few steps taken after the window.  Both compare the window's
program with the plain reference and say so in ``correct``; the numbers
compared and their limits are the last lines on stderr and the last key of
the result line.
"""
import time

T_START = time.perf_counter()

import argparse            # noqa: E402
import importlib           # noqa: E402
import json                # noqa: E402
import os                  # noqa: E402
import pathlib             # noqa: E402
import shutil              # noqa: E402
import sys                 # noqa: E402
import tempfile            # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".jax_cache"


class Cell:
    """One workload of BENCHMARK.json with its files read."""

    def __init__(self, spec: dict, name: str):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {sorted(cells)})")
        w = cells[name]
        configs = {c["name"]: c for c in spec["configs"]}
        self.name, self.chips = name, int(w["chips"])
        self.config = _json(ROOT / configs[w["config"]]["file"])
        self.traffic = _json(BENCH / "traffic" / f"{w['traffic']}.json")
        self.limits = _json(BENCH / "cells" / f"{name}.json")["limits"]
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in reported)]
        self.compile_clock = None


def _json(path):
    return json.loads(pathlib.Path(path).read_text())


class CompileClock:
    """Tracing, lowering and compiling, as jax.monitoring reports them."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events = []

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((time.perf_counter(), float(duration)))

    def count(self) -> int:
        return len(self.events)

    def seconds_before(self, t) -> float:
        return sum(d for at, d in self.events if at <= t)


def require_chip(chips: int) -> dict:
    """The device facts; raises unless JAX runs on enough TPU chips and the
    kernels resolve to compiled Pallas."""
    import jax
    from repro.kernels import dispatch
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: jax.devices()[0].platform is "
                           f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX sees "
                           f"{len(devs)}")
    backend = dispatch.default_backend()
    if backend != "pallas":
        raise RuntimeError(f"the kernels resolve to {backend!r}, not "
                           "'pallas'")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def use_compile_cache(jax):
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, for every program however
    quick to compile: only a cell's first run in a checkout compiles."""
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def load_reader(name: str):
    from bench import readers
    return readers.load(BENCH / "metrics" / f"{name}.py")


def per_layer(cell: Cell, res: dict, device: dict, compile_s: float) -> dict:
    """Each per-layer metric of the cell whose reader finds something."""
    from bench import counts
    ctx = {"trace": res.get("trace"), "traced_steps": res.get("traced_steps"),
           "end_to_end": res["end_to_end"], "chips": cell.chips,
           "peak": counts.peaks(device["kind"]), "compile_s": compile_s,
           **res["context"]}
    out = {}
    for m in cell.per_layer:
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise RuntimeError(f"no program (src/repro) under {ROOT}")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    cell = Cell(_json(ROOT / "BENCHMARK.json"), args.workload)

    # the TPU runtime's logs go under TMPDIR, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    from bench import compare, seeds, trace
    device = require_chip(cell.chips)
    use_compile_cache(jax)
    cell.compile_clock = clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    system = importlib.import_module(f"bench.systems.{cell.config['system']}")
    tracer = None
    if args.trace:
        def tracer(fn):
            try:
                return trace.record(fn, TRACE_DIR)
            finally:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
    res = system.measure(cell, seeds.seed_key(args.seed), args.seconds,
                         tracer)
    setup_s = res["setup_end"] - T_START
    compile_s = clock.seconds_before(res["setup_end"])
    device["memory_peak_bytes"] = int(res["memory_peak_bytes"])
    result = {"correct": None, "attempted": res["attempted"],
              "failed": res["failed"]}
    if args.trace:
        t = res["trace"]
        w = trace.window_ns(t)
        device["busy_s"] = sum(trace.busy_ns(t, p) for p in t["devices"]) \
            / len(t["devices"]) / 1e9
        device["window_s"] = w / 1e9
        result["metrics"] = per_layer(cell, res, device, compile_s)
        result["breakdown"] = {"device_ops": trace.top_ops(t),
                               "idle_gaps": trace.idle_gaps(t)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        values = dict(res["end_to_end"], setup_s=setup_s)
        result["metrics"] = {k: {"value": values[k], "unit": units[k]}
                             for k in units}
    result["device"] = device
    for phase, seconds in res["phases"].items():
        print(f"phase {phase} {seconds!r} s", file=sys.stderr, flush=True)
    checks = res["checks"]
    result["correct"] = all(compare.holds(v, lim) for _, v, lim in checks) \
        and res["failed"] == 0
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r} "
              f"{'ok' if compare.holds(v, lim) else 'FAIL'}",
              file=sys.stderr, flush=True)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main():
    try:
        result = run()
    except Exception as e:                               # noqa: BLE001
        import traceback
        traceback.print_exc()
        print(f"bench/run.py: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        sys.exit(1)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

"""Device time by stage of a step.

The program runs each stage of a step under a named scope: ``stage.grad``,
``stage.encode`` (the dither draw under ``stage.dither`` inside it),
``stage.gossip`` and ``stage.apply`` (dist/trainer.py ``make_train_step``,
core/engines/base.py ``_step_core``).  XLA keeps the scope as a whole
segment of each op's ``op_name`` metadata.  The reduced trace of
bench/trace.py names a device op by its HLO instruction alone, so this
module finds each instruction in the text of the compiled program that ran
(``program_text``: the cell's program built and compiled again as its
system builds it, which the persistent compile cache answers with the
executable the run used), keeps each event's ``op_name`` in a list beside
the trace's events (key ``op_names``), and sums self times by the innermost
``stage.*`` segment.  An op that XLA merged from ops of several scopes has
their names joined by ";", and counts under the last stage segment.

Record a scoped trace of a cell (one step of a training cell, one call of
an engine cell) on the chip, for bench/recorded/:

    python3 bench/stages.py --workload <cell> --out <file.json.gz> \\
        [--steps-per-call K]
"""
from __future__ import annotations

import gzip
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import trace  # noqa: E402

PREFIX = "stage."
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def stage_of(op_name: str):
    """The innermost ``stage.*`` segment of an op_name, without its prefix,
    or None."""
    segs = [s for s in op_name.split("/") if s.startswith(PREFIX)]
    return segs[-1][len(PREFIX):] if segs else None


def instructions(hlo_text: str) -> dict:
    """{instruction: (opcode, result element types, op_name)} over every
    instruction of a compiled module's HLO text (names are unique in a
    module)."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if not line.startswith("%") or " = " not in line:
            continue
        h = trace.hlo(line)
        m = _OP_NAME.search(line)
        out[h["inst"]] = (h["opcode"], h["results"], m.group(1) if m else "")
    return out


def op_name_of(event_name: str, insts: dict) -> str:
    """The op_name of a trace event's instruction, or "" when the module
    has no instruction of that name, opcode and result types (an op of
    another program the window ran)."""
    if " = " not in event_name:
        return ""
    h = trace.hlo(event_name)
    op, results, name = insts.get(h["inst"], (None, None, ""))
    return name if (op, results) == (h["opcode"], h["results"]) else ""


def attach(t: dict, hlo_text: str) -> dict:
    """The trace with ``op_names``: per chip, each event's op_name in the
    compiled module, in the order of ``t["devices"]``."""
    insts = instructions(hlo_text)
    return dict(t, op_names={
        plane: [op_name_of(n, insts) for n, _, _ in evs]
        for plane, evs in t["devices"].items()})


def stage_totals(t: dict, traced_steps: int) -> dict:
    """{stage: ms per step}, None keying the ops under no stage: the self
    time of the window's ops on each chip (a loop's body ops lie inside the
    loop's event), summed by stage, over the traced steps, averaged over
    the chips.  {} when the trace has no ``op_names``."""
    names = t.get("op_names")
    if not names or not traced_steps:
        return {}
    s, e = t["window"]
    tot = {}
    for plane, evs in t["devices"].items():
        ops = [(stage_of(n), max(a, s), min(b, e))
               for (_, a, b), n in zip(evs, names[plane])
               if min(b, e) > max(a, s)]
        for stage, ns in trace.self_times(ops):
            tot[stage] = tot.get(stage, 0) + ns
    chips = len(t["devices"])
    return {k: v / chips / traced_steps / 1e6 for k, v in tot.items()}


def stage_ms(t: dict, stage: str, traced_steps: int):
    """Milliseconds per step of the ops under ``stage.<stage>``, or None
    when the trace holds no op of that stage (or no op_names)."""
    return stage_totals(t, traced_steps).get(stage)


def save(t: dict, path):
    """A reduced trace with its op_names as gzipped JSON; trace.read reads
    it back, op_names included."""
    with gzip.open(path, "wt") as f:
        json.dump({k: t[k] for k in ("devices", "async", "host", "window",
                                     "op_names")}, f)


# ---------------------------------------------------------------------------
# the cell's compiled program
# ---------------------------------------------------------------------------

def cell_files(workload: str) -> tuple:
    """(configuration, traffic) of a cell of BENCHMARK.json."""
    from bench import run
    cell = run.Cell(json.loads((ROOT / "BENCHMARK.json").read_text()),
                    workload)
    return cell.config, cell.traffic


def built(config: dict, traffic: dict):
    """The cell's program as its system builds it, from a fixed seed: the
    compiled program does not depend on the seed."""
    import importlib

    from bench import seeds
    system = importlib.import_module(f"bench.systems.{config['system']}")
    return system.Program(config, traffic, seeds.seed_key(0))


def compiled_text(prog) -> str:
    """The optimized HLO text of the program's call (engine) or step
    (training), compiled for the arguments the window passes it."""
    import jax
    if hasattr(prog, "call_fn"):
        lowered = prog.call_fn.lower(prog.state, prog.a, prog.b, prog.skey)
    else:
        from bench.systems import train
        with jax.set_mesh(prog.run.mesh):
            lowered = prog.run.step_fn.lower(prog.state, prog.batch(0),
                                             train.step_key(prog.skey, 0))
    return lowered.compile().as_text()


def program_text(workload: str) -> str:
    """The compiled text of a cell's program: built, compiled (from the
    persistent cache when the run filled it) and freed."""
    prog = built(*cell_files(workload))
    try:
        return compiled_text(prog)
    finally:
        prog.free()


def _workload():
    """The cell bench/run.py was started for, or None."""
    argv = sys.argv
    if "--workload" in argv and argv.index("--workload") + 1 < len(argv):
        return argv[argv.index("--workload") + 1]
    return None


def named(t: dict):
    """The trace with op_names: as it is when it has them, else with those
    of the running cell's program, or None outside a bench/run.py
    process."""
    if "op_names" in t:
        return t
    workload = _workload()
    if workload is None:
        return None
    t0 = time.perf_counter()
    out = attach(t, program_text(workload))
    print(f"stages: op names from {workload}'s compiled program in "
          f"{time.perf_counter() - t0:.1f} s", file=sys.stderr, flush=True)
    return out


def read(ctx, stage: str):
    """A per-layer reader's value: the stage's milliseconds per step.  The
    named trace is kept in the run's ``ctx``, which every reader of the
    run is handed, so the program is compiled once a run."""
    if ctx["trace"] is None:
        return None
    if "stage_trace" not in ctx:
        ctx["stage_trace"] = named(ctx["trace"])
    t = ctx["stage_trace"]
    return None if t is None else stage_ms(t, stage, ctx["traced_steps"])


# ---------------------------------------------------------------------------
# recording a scoped trace on the chip
# ---------------------------------------------------------------------------

def record(workload: str, out, steps_per_call=None) -> tuple:
    """One steady step (training) or call (engine) of the cell's program,
    traced after a warm one, reduced, given its op_names and saved with no
    host span but the window; returns (the trace, its steps)."""
    import shutil

    import jax
    config, traffic = cell_files(workload)
    if steps_per_call:
        traffic = dict(traffic, steps_per_call=steps_per_call)
    prog = built(config, traffic)
    one = prog.call if hasattr(prog, "call_fn") else lambda: prog.step(0)
    one()
    jax.block_until_ready(prog.state)

    def traced():
        one()
        jax.block_until_ready(prog.state)

    directory = ROOT / ".bench_trace"
    try:
        t = trace.record(traced, directory)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
    t = attach(t, compiled_text(prog))
    steps = getattr(prog, "K", 1)
    prog.free()
    t["host"] = [h for h in t["host"] if h[0] == trace.WINDOW]
    save(t, out)
    return t, steps


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps-per-call", type=int, default=None)
    args = ap.parse_args(argv)
    import jax

    from bench import run
    run.use_compile_cache(jax)
    t, steps = record(args.workload, args.out, args.steps_per_call)
    totals = stage_totals(t, steps)
    busy = sum(totals.values())
    print(json.dumps({"workload": args.workload, "steps": steps,
                      "ms_per_step": {str(k): v for k, v in totals.items()},
                      "scoped_share": 1 - totals.get(None, 0.0) / busy
                      if busy else None,
                      "top_ops": trace.top_ops(t, 15)}))


if __name__ == "__main__":
    main()

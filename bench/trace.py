"""From a profiler trace to the per-layer metrics' raw numbers.

``record`` traces a function with the JAX profiler and ``load`` reduces the
``.xplane.pb`` it writes to plain event lists: per device, the operations
the chip ran, as (name, start_ns, end_ns); and on the host, the spans the
benchmark annotated and the Python frames under them.  Everything after
``load`` is arithmetic on those lists, so the tests check it on synthetic
events and on a trace recorded on the chip (bench/recorded/).

Device time is the union of the intervals in which an operation runs, so
nested or overlapping events count once; the window is the host span
``window`` that ``record`` puts around the traced function.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import pathlib
import re
import shutil

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "window"
COLLECTIVE_MARKS = ("collective-permute", "all-reduce", "all-gather",
                    "reduce-scatter", "all-to-all")


def record(fn, directory) -> dict:
    """Run ``fn`` under the profiler, inside a host span ``window``; returns
    ``load`` of the trace (``directory`` is emptied first)."""
    import jax
    directory = pathlib.Path(directory)
    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True)
    with jax.profiler.trace(str(directory)):
        with jax.profiler.TraceAnnotation(WINDOW):
            fn()
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, found "
                           f"{paths}")
    return load(paths[0])


def load(path) -> dict:
    """{"devices": {plane: [(op, start, end)]}, "async": {plane: [...]},
    "host": [(name, start, end)], "window": (start, end)}, times in ns on
    the trace's one clock.  An op's name is its HLO instruction as the
    trace gives it (``%fusion.3 = f32[...] fusion(...)``); the profiler's
    events are flat, so a loop and the ops of its body overlap."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices, async_ops, host, window = {}, {}, [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                evs = sorted((compact(e.name), int(e.start_ns),
                              int(e.end_ns)) for e in line.events)
                if line.name == OPS_LINE:
                    devices[plane.name] = evs
                elif line.name == ASYNC_LINE:
                    async_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, int(e.start_ns), int(e.end_ns))
                    host.append(ev)
                    if e.name == WINDOW and window is None:
                        window = ev[1:]
    if not devices:
        raise RuntimeError(f"no {OPS_LINE!r} line on any {DEVICE_PLANE} plane "
                           f"in {path}")
    if window is None:
        raise RuntimeError(f"no host span {WINDOW!r} in {path}")
    return {"devices": devices, "async": async_ops,
            "host": sorted(host, key=lambda e: e[1]), "window": window}


def save(trace: dict, path):
    """The reduced trace as gzipped JSON (what bench/recorded/ holds)."""
    with gzip.open(path, "wt") as f:
        json.dump({k: trace[k] for k in ("devices", "async", "host",
                                         "window")}, f)


def read(path) -> dict:
    with gzip.open(path, "rt") as f:
        t = json.load(f)
    for lines in ("devices", "async"):
        t[lines] = {k: [tuple(e) for e in v] for k, v in t[lines].items()}
    t["host"] = [tuple(e) for e in t["host"]]
    t["window"] = tuple(t["window"])
    return t


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> list:
    """The parts of the intervals ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


# ---------------------------------------------------------------------------
# the reductions the per-layer readers use
# ---------------------------------------------------------------------------

def window_ns(trace) -> int:
    s, e = trace["window"]
    return e - s


def _ops(trace, plane, line=OPS_LINE):
    s, e = trace["window"]
    evs = (trace["devices"] if line == OPS_LINE else
           trace.get("async", {})).get(plane, [])
    return [(n, max(a, s), min(b, e)) for n, a, b in evs
            if min(b, e) > max(a, s)]


def busy_ns(trace, plane) -> int:
    """Time in the window in which some operation runs on this chip."""
    return length((a, b) for _, a, b in _ops(trace, plane))


def idle_share(trace) -> float:
    """The largest idle share over the chips: 1 - busy / window."""
    w = window_ns(trace)
    return max(1.0 - busy_ns(trace, p) / w for p in trace["devices"])


def hlo(name: str) -> dict:
    """The parts of an op's HLO text the readers match on: instruction,
    opcode, result element types, operand count and custom-call target."""
    inst, _, rest = name.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 2:]
    else:
        result, _, rest = rest.partition(" ")
    opcode, _, args = rest.partition("(")
    depth, operands = 1, 0
    for c in args:
        depth += (c == "(") - (c == ")")
        if depth == 0:
            break
        operands += c == "%"
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return {"inst": inst.lstrip("%"), "opcode": opcode, "result": result,
            "results": tuple(re.findall(r"\b([a-z]+[0-9]*)\[", result)),
            "operands": operands, "target": target and target.group(1)}


def compact(name: str) -> str:
    """An op's HLO text cut to what ``hlo`` reads: instruction, result,
    opcode, one ``%`` per operand and the custom-call target."""
    if " = " not in name:
        return name
    h = hlo(name)
    out = (f"%{h['inst']} = {h['result']} {h['opcode']}("
           f"{', '.join(['%'] * h['operands'])})")
    return out + (f', custom_call_target="{h["target"]}"' if h["target"]
                  else "")


def matches(name: str, kernel: dict) -> bool:
    """A Pallas call shows in the trace as a ``tpu_custom_call`` named after
    the jitted function around it, not after its kernel; a kernel is
    known by its signature: the target, the operand count and the result
    element types."""
    if "custom-call(" not in name:
        return False
    h = hlo(name)
    return (h["target"] == kernel["target"]
            and h["operands"] == kernel["operands"]
            and h["results"] == tuple(kernel["results"]))


def kernel_events(trace, plane, kernel) -> list:
    return [(n, a, b) for n, a, b in _ops(trace, plane) if matches(n, kernel)]


def kernel_ns(trace, plane, kernel) -> int:
    """Summed device time of the kernel's events on this chip."""
    return sum(b - a for _, a, b in kernel_events(trace, plane, kernel))


def is_collective(name: str) -> bool:
    return any(mark in name for mark in COLLECTIVE_MARKS)


def _collectives(trace, plane) -> list:
    """Collective intervals: the ops themselves and, for asynchronous
    ones, their time in flight (the trace's async line)."""
    ops = _ops(trace, plane) + _ops(trace, plane, ASYNC_LINE)
    return [(a, b) for n, a, b in ops if is_collective(n)]


def collective_ns(trace, plane) -> int:
    return length(_collectives(trace, plane))


def exposed_collective_ns(trace, plane) -> int:
    """Time in which a collective runs on this chip and nothing else does."""
    coll = _collectives(trace, plane)
    other = [(a, b) for n, a, b in _ops(trace, plane)
             if not is_collective(n)]
    return sum(e - s for s, e in subtract(coll, other))


def self_times(events) -> list:
    """[(name, self ns)]: each op's time less the ops nested inside it (a
    loop's body ops lie inside the loop's interval)."""
    out, stack = [], []
    for n, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            out.append(stack.pop()[::3])
        if stack:
            stack[-1][3] -= min(b, stack[-1][2]) - a
        stack.append([n, a, b, b - a])
    out.extend(e[::3] for e in reversed(stack))
    return [(n, t) for n, t in out]


def short_name(name: str) -> str:
    """``fusion.616 fusion``, or for a custom call its target and
    signature (``step.23 tpu_custom_call(10)->f32,f32,f32,f32``)."""
    h = hlo(name)
    if h["target"]:
        return (f"{h['inst']} {h['target']}({h['operands']})->"
                f"{','.join(h['results'])}")
    return f"{h['inst']} {h['opcode']}"


def top_ops(trace, k=10) -> list:
    """[(op, seconds)]: the ops with the most self time in the window,
    averaged over the chips."""
    tot = {}
    for plane in trace["devices"]:
        for n, t in self_times(_ops(trace, plane)):
            key = short_name(n)
            tot[key] = tot.get(key, 0) + t
    chips = len(trace["devices"])
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, t / chips / 1e9] for n, t in top]


def idle_gaps(trace, k=10) -> list:
    """[(what the host was doing, seconds)]: the longest gaps on the first
    chip in which no operation runs, each named by the shortest host span
    that covers the gap's middle."""
    plane = sorted(trace["devices"])[0]
    s, e = trace["window"]
    busy = [(a, b) for _, a, b in _ops(trace, plane)]
    gaps = subtract([(s, e)], busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for gs, ge in gaps[:k]:
        mid = (gs + ge) // 2
        spans = [h for h in trace["host"] if h[1] <= mid < h[2]
                 and h[0] != WINDOW]
        label = min(spans, key=lambda h: h[2] - h[1])[0] if spans else "?"
        out.append([label, (ge - gs) / 1e9])
    return out

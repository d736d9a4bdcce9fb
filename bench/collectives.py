"""The gossip's collectives in a device trace: their time, the part of it
that no other op covers, and the bytes they send.

A collective shows on a chip's op line as ``collective-permute`` or as its
``-start`` and ``-done`` halves (or an all-reduce, all-gather, ...).  On a
TPU v5e 2x2 the profiler records an asynchronous collective's flight, from
start to done, on the async line of the first chip's plane alone, so the
collective time of every chip is that of the ops themselves: the time the
chip spends issuing collectives and waiting on them.  The exposed part is
what no other op of the chip covers of the ops and, where the trace has
them, of their flights (bench/trace.py).  An event's name is its
instruction's text in the compiled program, the result's layout included,
so the bytes a collective sends are read from it: the buffer of a
``-start`` op (the first element of its result) or of a synchronous op,
padded to the first tile of its layout.  Each reading is per step,
averaged over the chips, and None when the traced steps ran no collective.
"""
from __future__ import annotations

import re

from bench import trace

DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
               "u32": 4, "f32": 4}
SENDING = ("collective-permute", "collective-permute-start", "all-reduce",
           "all-reduce-start", "all-gather", "all-gather-start")
_SHAPE = re.compile(r"([a-z]+[0-9a-z]*)\[([0-9,]*)\](?:\{([^}]*)\})?")


def buffer_bytes(shape: str) -> float:
    """Bytes of one array as its layout keeps it: ``s8[1,8192,512]{2,1,0:
    T(8,128)(4,1)}`` pads the two most minor dimensions to 8 and 128."""
    m = _SHAPE.match(shape.strip())
    if m is None:
        raise ValueError(f"not an array shape: {shape!r}")
    dtype, dims, layout = m.groups()
    dims = [int(x) for x in dims.split(",") if x]
    minor_to_major, _, tiling = (layout or "").partition(":")
    order = [int(x) for x in minor_to_major.split(",") if x]
    physical = [dims[i] for i in reversed(order)] if order else dims
    tile = re.match(r"T\(([0-9,]+)\)", tiling)
    if tile:
        tile = [int(x) for x in tile.group(1).split(",")]
        physical = [1] * (len(tile) - len(physical)) + physical
        for k, t in enumerate(tile, start=len(physical) - len(tile)):
            physical[k] = -(-physical[k] // t) * t
    n = 1
    for x in physical:
        n *= x
    return n * DTYPE_BYTES[dtype]


def sent_bytes(name: str) -> float:
    """The bytes a collective op's event sends (0 for a ``-done`` half and
    for every other op)."""
    if " = " not in name:
        return 0.0
    h = trace.hlo(name)
    if h["opcode"] not in SENDING:
        return 0.0
    result = h["result"]
    if result.startswith("("):
        result = result[1:]
    return buffer_bytes(result)


def _per_chip(t, fn):
    vals = [fn(plane) for plane in t["devices"]]
    return sum(vals) / len(vals)


def _op_ns(t, plane) -> int:
    """Device time of a chip's collective ops themselves."""
    return trace.length((a, b) for n, a, b in trace._ops(t, plane)
                        if trace.is_collective(n))


def _ran(t) -> bool:
    return any(_op_ns(t, p) for p in t["devices"])


def collective_ms(t, steps: int):
    """Milliseconds per step in which a collective op runs on a chip."""
    if not _ran(t):
        return None
    return _per_chip(t, lambda p: _op_ns(t, p)) / steps / 1e6


def exposed_share(t):
    """% of the traced window in which a collective runs on a chip and no
    other op does."""
    if not _ran(t):
        return None
    return 100.0 * _per_chip(t, lambda p: trace.exposed_collective_ns(t, p)) \
        / trace.window_ns(t)


def sent_per_step(t, steps: int):
    """Bytes a chip's collective ops send per step."""
    if not _ran(t):
        return None
    return _per_chip(t, lambda p: sum(
        sent_bytes(n) for n, _, _ in trace._ops(t, p))) / steps

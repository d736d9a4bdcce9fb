"""dither_ms.engine: milliseconds per step of the device ops under
stage.dither (core/engines/base.py _dither_plane, the quantizer's U[0,1)
draw), by self time in the traced steps (bench/stages.py).  Moves
engine_steps_per_s."""
from bench import stages


def read(ctx):
    return stages.read(ctx, "dither")

"""encode_ms.train: milliseconds per step of the device ops under
stage.encode and not under a stage nested in it (the blockify, the
engine's message and the wire encode), by self time in the traced steps
(bench/stages.py).  Moves train_tokens_per_s."""
from bench import stages


def read(ctx):
    return stages.read(ctx, "encode")

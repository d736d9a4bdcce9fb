"""apply_ms.train: milliseconds per step of the device ops under
stage.apply (the engine's apply_stage with the lead_update kernel, the
unblocking and the hierarchical projection), by self time in the traced
steps (bench/stages.py).  Moves train_tokens_per_s."""
from bench import stages


def read(ctx):
    return stages.read(ctx, "apply")

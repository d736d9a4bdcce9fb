"""gossip_ms.train: milliseconds per step of the device ops under
stage.gossip (the fault masks, the decode, the ppermute exchange and the
neighbour mix), by self time in the traced steps (bench/stages.py).  Moves
train_tokens_per_s."""
from bench import stages


def read(ctx):
    return stages.read(ctx, "gossip")

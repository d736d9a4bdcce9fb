"""exposed_collective_share.train: the share of the traced window, in %,
in which a collective runs on a chip and no other op does: the gossip's
time that compute does not hide, averaged over the chips
(bench/collectives.py).  Moves train_tokens_per_s."""
from bench import collectives


def read(ctx):
    t = ctx["trace"]
    return None if t is None else collectives.exposed_share(t)


"""collective_ms.train: milliseconds per step in which a collective op (the
gossip's collective-permute halves, the gradient norm's all-reduce) runs
on a chip, from the device trace of the traced steps, averaged over the
chips (bench/collectives.py); an op's flight between its halves is not
counted.  Moves train_tokens_per_s."""
from bench import collectives


def read(ctx):
    t = ctx["trace"]
    return None if t is None else collectives.collective_ms(
        t, ctx["traced_steps"])

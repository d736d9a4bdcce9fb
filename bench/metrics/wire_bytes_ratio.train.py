"""wire_bytes_ratio.train: the bytes one chip's collective ops send in a
step, each op's buffer as its layout keeps it (bench/collectives.py), over
the payload the quantizer meters for that chip: the step's wire bits (as
the window's steps report them) / 8 for each neighbour it sends to.  1 when
the wire carries what is metered; int8 codes for 3 metered bits an element
read about 8/3.  Moves train_tokens_per_s."""
from bench import collectives


def read(ctx):
    t, metered = ctx["trace"], ctx.get("metered_wire_bytes_per_step_per_chip")
    if t is None or not metered:
        return None
    sent = collectives.sent_per_step(t, ctx["traced_steps"])
    return None if sent is None else sent / metered

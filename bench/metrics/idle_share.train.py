"""idle_share: 1 - (union of the device's op intervals / traced window), the
largest over the chips, in %, from the device trace."""
from bench import trace


def read(ctx):
    if ctx["trace"] is None:
        return None
    return 100.0 * trace.idle_share(ctx["trace"])

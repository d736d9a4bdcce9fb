"""mfu.train: the model's FLOPs per trained token (bench/counts.py, no
recompute) times the window's tokens per second, over the chips' bf16
peak.  Moves train_tokens_per_s."""


def read(ctx):
    tps = ctx["end_to_end"].get("train_tokens_per_s")
    if tps is None:
        return None
    peak = ctx["peak"]["bf16_flop_per_s"] * ctx["chips"]
    return 100.0 * ctx["flops_per_token"] * tps / peak

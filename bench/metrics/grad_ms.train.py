"""grad_ms.train: milliseconds per step of the device ops under the
program's stage.grad scope (the vmapped forward and backward, the f32
cast, the optimizer and the gradient norm in dist/trainer.py
make_train_step), by self time in the traced steps (bench/stages.py).
Moves train_tokens_per_s."""
from bench import stages


def read(ctx):
    return stages.read(ctx, "grad")

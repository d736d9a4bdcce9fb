"""compile_s: seconds of tracing, lowering and compiling during set-up, as
jax.monitoring reports them (warm runs read the executables back from the
persistent cache).  Moves setup_s."""


def read(ctx):
    return ctx["compile_s"]

"""What several per-layer readers share."""
import importlib.util
import pathlib

from bench import counts, trace

METRICS = pathlib.Path(__file__).resolve().parent / "metrics"


def load(path):
    """A per-layer metric's reader module, from its file."""
    path = pathlib.Path(path)
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def same_as(name: str):
    """The ``read`` of bench/metrics/<name>.py: a metric split by suffix
    for another family of cells reads the same quantity with it."""
    return load(METRICS / f"{name}.py").read


def roofline(ctx, kernel, bytes_key, calls_key):
    """A kernel's share of its roofline: the least time the chip could take
    for the bytes its calls must move (their FLOPs are negligible for these
    elementwise kernels) over the summed device time of its events, per
    chip, averaged over the chips.  None when the trace holds no event of
    the kernel."""
    t = ctx["trace"]
    if t is None:
        return None
    steps = ctx["traced_steps"]
    shares = []
    for plane in t["devices"]:
        events = trace.kernel_events(t, plane, kernel)
        if not events:
            return None
        want = steps * ctx[calls_key]
        if len(events) != want:
            raise RuntimeError(f"{len(events)} {kernel['name']} events in the "
                               f"trace, {want} calls expected")
        seconds = sum(b - a for _, a, b in events) / 1e9
        value, _bound = counts.roofline_share(steps * ctx[bytes_key], 0.0,
                                              seconds, ctx["peak"])
        shares.append(value)
    return sum(shares) / len(shares)

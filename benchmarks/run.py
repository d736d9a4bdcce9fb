"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Modules:
    bench_linreg        Fig 1  (linear regression, ring-8)
    bench_theory        Remark 5 (bit-width sweep) + Corollary 1 (kappa_g)
    bench_logreg        Fig 2/3 + App. D.2 (logistic regression, het/hom)
    bench_compression   Fig 5/6 (p-norm quantization error, methods) + kernels
    bench_sensitivity   Fig 7  (alpha x gamma robustness grid)
    bench_nn            Fig 4 proxy (non-convex LM, hom/het)
    bench_lead_step     flat-buffer engine vs pytree path step latency
    bench_baselines     flat engine family vs tree baselines (Fig 2-4 sweep)
    bench_gossip        dense vs neighbor-exchange mixing at n in {8,32,128}
    bench_faults        masked degraded mixing overhead vs the clean path
    bench_serve         continuous batching + quantized paged-KV serving

``--json OUT``: additionally write one machine-readable ``BENCH_<name>.json``
per executed module into directory OUT (rows: name, us_per_call, derived) so
the perf trajectory is comparable across PRs.  Writes go through
``common.write_json`` — temp file + JSON round-trip validation + atomic
rename, stamped with the machine/runtime ``env`` block — so a crashed or
concurrent bench never leaves a truncated BENCH file, and numbers from
different hosts are never diffed blind.
"""
import os
import sys
import traceback

from benchmarks import (bench_baselines, bench_compression, bench_faults,
                        bench_gossip, bench_lead_step, bench_linreg,
                        bench_logreg, bench_nn, bench_sensitivity,
                        bench_serve, bench_theory)
from benchmarks.common import drain_rows, write_json
from repro.utils.compile_cache import use_compile_cache

ALL = {
    "linreg": bench_linreg.main,
    "logreg": bench_logreg.main,
    "compression": bench_compression.main,
    "sensitivity": bench_sensitivity.main,
    "nn": bench_nn.main,
    "theory": bench_theory.main,
    "lead_step": bench_lead_step.main,
    "baselines": bench_baselines.main,
    "gossip": bench_gossip.main,
    "faults": bench_faults.main,
    "serve": bench_serve.main,
}


def main() -> None:
    args = sys.argv[1:]
    json_dir = None
    if "--json" in args:
        i = args.index("--json")
        try:
            json_dir = args[i + 1]
        except IndexError:
            print("--json requires an output directory", file=sys.stderr)
            sys.exit(2)
        del args[i:i + 2]
        os.makedirs(json_dir, exist_ok=True)

    names = args or list(ALL)
    use_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for n in names:
        drain_rows()  # isolate each module's rows
        try:
            ALL[n]()
            if json_dir is not None:
                write_json(os.path.join(json_dir, f"BENCH_{n}.json"),
                           n, drain_rows())
        except Exception:
            failed.append(n)
            traceback.print_exc()
    if failed:
        print(f"FAILED benchmarks: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()

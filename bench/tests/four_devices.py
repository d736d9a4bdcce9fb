"""Checks that need four devices, run in a process of their own on four
CPU devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``):

    python3 bench/tests/four_devices.py <workdir>

Prints one JSON line: the spread reference against the stacked one on a
tiny ring of four, the gossip cut of bench/calibrate.py planted in the
trainer, and whole runs of a tiny four-chip cell, sound and broken.
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax                                       # noqa: E402
import jax.numpy as jnp                          # noqa: E402
import numpy as np                               # noqa: E402

from bench import calibrate, compare, seeds      # noqa: E402
from bench.systems import train                  # noqa: E402
from bench.tests import harness, stacked_reference  # noqa: E402

CELL = "tiny.train.ring4"


def reference_gaps(config, traffic, seed):
    """The largest relative gap of each number between the reference
    spread over the four devices and stacked on one."""
    key = seeds.seed_key(seed)
    spread = train.reference_readings(config, traffic, key)
    homes = {next(iter(l.devices())) for l in
             jax.tree_util.tree_leaves(spread["x"])}
    stacked = stacked_reference.reference_readings(config, traffic, key)
    gaps = {k: compare.rel_gap(spread[k], stacked[k])
            for k in ("loss", "grad_norm", "first_grad", "bits")}
    for f in stacked["change"]:
        gaps["change_" + f] = compare.rel_gap(spread["change"][f],
                                              stacked["change"][f])
    gaps["x"] = train.distance(jax.device_get(stacked["x"]), spread["x"])
    return {"devices": len(homes), "gaps": gaps}


def gossip_cut(config, traffic, seed):
    """max |hw - h| over every leaf after one step, sound and with the
    gossip cut: from the consensus start (h = hw) the cut leaves each
    agent's hw moved by its own payload alone, as its h."""
    out = {}
    for kind, cls in (("sound", train.Program),
                      ("gossip_cut", calibrate.programs(
                          "train", train, traffic["agents"])["gossip_cut"])):
        prog = cls(config, traffic, seeds.seed_key(seed))
        prog.step(0)
        out[kind] = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(prog.state.algo["hw"]),
            jax.tree_util.tree_leaves(prog.state.algo["h"])))
        prog.free()
    return out


def runs(workdir):
    """correct and the failing checks of a whole run of the tiny four-chip
    cell, sound and with each fault in the trainer's place."""
    root = harness.checkout(pathlib.Path(workdir), cells=(CELL,))
    run_mod = harness.load_run(root)
    run_mod.require_chip = lambda chips: dict(harness.FAKE_CHIP,
                                              count=chips)
    alts = calibrate.programs("train", train, 4)
    sound, out = train.Program, {}
    for kind in ("sound", "gossip_cut", "half_batch", "still_in_window"):
        train.Program = sound if kind == "sound" else alts[kind]
        try:
            r = run_mod.run(["--workload", CELL, "--seed", str(2 ** 33 + 5),
                             "--seconds", "0.3", "--trace", "0"])
        finally:
            train.Program = sound
        out[kind] = {"correct": r["correct"], "failing": sorted(
            k for k, c in r["checks"].items()
            if not compare.holds(c["value"], c["limit"]))}
    return out


def main(workdir):
    _, config, _, traffic, _ = harness.TINY_CELLS[CELL]
    print(json.dumps({"device_count": jax.device_count(),
                      "reference": reference_gaps(config, traffic, 2 ** 40 + 3),
                      "gossip_cut": gossip_cut(config, traffic, 11),
                      "runs": runs(workdir)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1])

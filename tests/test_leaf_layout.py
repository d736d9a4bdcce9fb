"""The trainer's leaf layout: each stacked leaf whose last dim is a
multiple of the quantization block reaches the engine as (A, rows, last
dim), a bitcast of the leaf on the TPU, instead of its flattening padded
to (A, nb, block) (dist/trainer.py ``_leaf_view``).

The equivalence cases run tests/dist_worker.py's ``leaf_layout`` on 4
placeholder devices, one subprocess per case, all started together.
"""
import dataclasses
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
WORKER = ROOT / "tests" / "dist_worker.py"
CASES = ("ring", "wire_pack", "hierarchical", "comm_interval", "randk",
         "choco", "pallas")
FIELDS = {"choco": ["bits_per_agent", "params", "xhat", "xhat_w"]}
LEAD_FIELDS = ["bits_per_agent", "d", "h", "hw", "params"]


@pytest.fixture(scope="module")
def layout_runs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(ROOT / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    procs = {c: subprocess.Popen(
        [sys.executable, str(WORKER), "leaf_layout", c], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for c in CASES}
    out = {}
    for c, p in procs.items():
        stdout, stderr = p.communicate(timeout=900)
        assert p.returncode == 0, f"{c}:\n{stdout[-3000:]}\n{stderr[-3000:]}"
        line = [l for l in stdout.splitlines() if l.startswith("LEAF_LAYOUT ")]
        out[c] = json.loads(line[-1][len("LEAF_LAYOUT "):])
    return out


@pytest.mark.parametrize("case", CASES)
def test_tiled_view_equals_padded_path_bit_for_bit(layout_runs, case):
    """Three LEAD steps (CHOCO-SGD's in "choco") with each leaf on its
    tiled view leave params, the engine's state fields and each step's
    bits_per_agent exactly as the padded path does; the kv projections (128
    lanes) and the head (256) stay padded.  RandK's blocks are not lane
    segments, so every leaf keeps the padded path.

    "pallas" runs the kernels in interpret mode, the quantize kernel on
    each device's own agent inside the trainer's shard_map.  There the
    padded path decodes in XLA, which the CPU fuses into the gossip mix
    with contracted multiply-adds, and the tiled path's decode kernel
    does not: the two differ in the last bit, and a 2-bit level sitting on
    its edge then flips.  So the bits must be equal and at most 1e-5 of
    the state's elements may miss by more than 1e-4 of the largest
    parameter; a wrong agent key or a wrong row would move most of them."""
    r = layout_runs[case]
    assert r["fields"] == FIELDS.get(case, LEAD_FIELDS)
    assert r["bits"] == r["padded_bits"]
    if case == "pallas":
        assert r["deviating"] <= 1e-5 * r["elements"], (
            r["deviating"], r["elements"])
    else:
        assert r["differ"] == [], r["differ"]
    assert r["padded_layout"]["tiled_leaves"] == 0
    n = r["padded_layout"]["padded_leaves"]
    total = r["padded_layout"]["padded_elements"]
    if case == "randk":
        assert r["layout"] == r["padded_layout"]
    else:
        assert r["layout"]["tiled_leaves"] == n - 3    # wk, wv, lm_head
        assert r["layout"]["padded_elements"] < total
        assert (r["layout"]["tiled_elements"]
                + r["layout"]["padded_elements"]) == total
    if case == "comm_interval":
        assert r["bits"][1] == 0.0 and r["bits"][0] == r["bits"][2] > 0
    else:
        assert all(b > 0 for b in r["bits"])


def test_granite_leaves_all_take_the_tiled_view():
    """The benchmark's granite-3-2b configuration (bench/configs): all 11
    leaves on the tiled view, nothing padded — counted when the step is
    made, and the same from the shapes of the state it is traced with,
    abstractly."""
    from jax.sharding import AxisType

    from repro.configs.registry import get_config
    from repro.dist import sharding as shr
    from repro.dist.trainer import (DistConfig, _layout_counts, _leaf_view,
                                    engine_of, init_train_state,
                                    make_train_step)

    conf = json.loads(
        (ROOT / "bench/configs/granite3-2b-L4-lead2.json").read_text())
    m, alg = conf["model"], conf["algorithm"]
    cfg = dataclasses.replace(get_config(conf["arch"]),
                              n_layers=m["n_layers"],
                              tie_embeddings=m["tie_embeddings"])
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    prof = shr.make_profile(cfg, mesh.axis_names)
    shr.set_mesh_for_rules(mesh)
    dc = DistConfig(algorithm=alg["name"], bits=alg["bits"],
                    block=alg["block"])
    want = {"tiled_leaves": 11, "tiled_elements": 343_957_504,
            "padded_leaves": 0, "padded_elements": 0}
    step = make_train_step(cfg, mesh, prof, dc)
    assert step.layout == want
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    leaves = jax.tree_util.tree_leaves(state.params)
    comp = engine_of(dc, 1).compressor
    views = [_leaf_view(l.shape, dc.block, comp) for l in leaves]
    assert _layout_counts(leaves, views) == want

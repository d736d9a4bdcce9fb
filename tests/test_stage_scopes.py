"""The step's stages carry their names into the compiled program: every op
of the trainer step and of the flat engine's step_with_wire lies under a
``stage.*`` segment of its op_name metadata, which is what lets a profiler
trace put device time down to a stage; and launch/train.loop marks each
step on the profiler's clock."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

TRAIN_STAGES = {"stage.grad", "stage.encode", "stage.gossip", "stage.apply"}
ENGINE_STAGES = {"stage.encode", "stage.dither", "stage.gossip",
                 "stage.apply"}


def _op_names(hlo_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _stage(op_name: str):
    """The innermost stage.* segment of an op_name, or None."""
    segs = [s for s in op_name.split("/") if s.startswith("stage.")]
    return segs[-1] if segs else None


@pytest.fixture(scope="module")
def run():
    """One agent, LEAD 2-bit, the reduced granite config cut to a layer."""
    from repro.launch import train
    return train.build(train.parse_args([
        "--mesh-shape", "1,1", "--reduced", "--layers", "1", "--seq-len",
        "32", "--batch-per-agent", "1", "--steps", "2", "--log-every",
        "100"]))


def test_trainer_step_names_its_stages(run):
    batch = run.get_batch(0)
    with jax.set_mesh(run.mesh):
        text = run.step_fn.lower(run.state, batch, jax.random.fold_in(
            run.key, 0)).compile().as_text()
    names = _op_names(text)
    found = {_stage(n) for n in names}
    assert TRAIN_STAGES <= found, sorted(s for s in found if s)
    # the backward pass (the transposed ops) lies under stage.grad
    backward = [n for n in names if "transpose(" in n]
    assert backward
    assert {_stage(n) for n in backward} == {"stage.grad"}
    # the stage is a whole segment of the step's name stack
    assert any(n.startswith("jit(step)/stage.grad/") for n in names)


def test_engine_step_names_its_stages():
    from repro.core import topology
    from repro.core.compression import QuantizePNorm
    from repro.core.engines import engine_for

    eng = engine_for(topology.ring(4), QuantizePNorm(bits=2, block=512),
                     4096)
    x0 = jnp.ones((4, 4096), jnp.float32)
    state = eng.init(x0, x0, None)
    text = jax.jit(eng.step_with_wire).lower(
        state, eng.blockify(x0), jax.random.PRNGKey(0)).compile().as_text()
    names = _op_names(text)
    found = {_stage(n) for n in names}
    assert ENGINE_STAGES <= found, sorted(s for s in found if s)
    # the dither draw nests inside the encode
    dither = [n for n in names if _stage(n) == "stage.dither"]
    assert all("/stage.encode/stage.dither/" in n for n in dither)


def test_train_loop_marks_each_step_on_the_profiler_clock(run, tmp_path):
    from jax.profiler import ProfileData

    from repro.launch import train

    with jax.profiler.trace(str(tmp_path)):
        train.loop(run)
    jax.block_until_ready(run.state)
    paths = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    steps, fetches = [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "train":
                    steps.append((e.start_ns, e.end_ns,
                                  {k: v for k, v in e.stats}))
                elif e.name == "train.get_batch":
                    fetches.append((e.start_ns, e.end_ns))
    assert len(steps) == 2 and len(fetches) == 2
    assert sorted(int(s[2]["step_num"]) for s in steps) == [0, 1]
    # each batch fetch lies inside its step's span
    for (s0, s1, _), (f0, f1) in zip(sorted(steps), sorted(fetches)):
        assert s0 <= f0 <= f1 <= s1

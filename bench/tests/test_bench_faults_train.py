"""A whole training run at a tiny size, sound and with its timed path broken
underneath: ``correct`` holds for the sound run and fails for each fault."""
import jax
import jax.numpy as jnp
import pytest

from bench.tests import harness


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = harness.checkout(tmp_path_factory.mktemp("train"),
                            cells=("tiny.train",))
    return harness.load_run(root)


def _break(monkeypatch, fault):
    """Wrap the jitted step that launch/train builds."""
    from repro.launch import train as train_mod
    from bench.systems import train
    real_build = train_mod.build

    def build(args):
        run = real_build(args)
        real = run.step_fn
        calls = []

        def unchanged(state, batch, key):
            _, metrics = real(jax.tree_util.tree_map(jnp.copy, state),
                              batch, key)
            return state, metrics

        def half_batch(state, batch, key):
            # half of the batch left out, the mean taken over the rest
            half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
            return real(state, half, key)

        def altered(state, batch, key):
            # an answer altered where it is produced: one leaf of the new
            # iterate
            new, metrics = real(state, batch, key)
            p = dict(new.params)
            p["final_ln"] = p["final_ln"] * 1.001
            return new._replace(params=p), metrics

        def unchanged_in_window(state, batch, key):
            # the checked steps sound, every later one returning its state
            calls.append(None)
            if len(calls) <= train.CHECKED_STEPS:
                return real(state, batch, key)
            return unchanged(state, batch, key)

        run.step_fn = {"unchanged": unchanged, "half_batch": half_batch,
                       "altered": altered,
                       "unchanged_in_window": unchanged_in_window}[fault]
        return run

    monkeypatch.setattr(train_mod, "build", build)


def test_sound_run_is_correct(checkout, monkeypatch):
    result = harness.drive(checkout, monkeypatch, "tiny.train", seed=2**40)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered",
                                   "unchanged_in_window"])
def test_a_broken_step_is_not_correct(checkout, monkeypatch, fault):
    _break(monkeypatch, fault)
    result = harness.drive(checkout, monkeypatch, "tiny.train", seed=7)
    assert result["correct"] is False, (fault, result["checks"])
    if fault == "unchanged_in_window":
        # the checked steps were sound: only the window's check catches it
        failing = {k for k, c in result["checks"].items()
                   if not c["value"] <= c["limit"]}
        assert failing == {"window_stall"}, result["checks"]


@pytest.mark.parametrize("kind", ["control", "control_compute",
                                  "half_batch", "still_in_window"])
def test_the_control_is_not_correct(checkout, monkeypatch, kind):
    """The trainer's own lower-precision paths (bf16 state and compute;
    bf16 compute alone) and the planted faults in its place, as
    bench/calibrate.py runs them on the chip."""
    from bench import calibrate
    from bench.systems import train
    control = calibrate.programs("train", train)[kind]
    monkeypatch.setattr(train, "Program", control)
    result = harness.drive(checkout, monkeypatch, "tiny.train", seed=9)
    assert result["correct"] is False, result["checks"]

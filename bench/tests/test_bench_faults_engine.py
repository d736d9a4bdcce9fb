"""A whole engine run at a tiny size, sound and with its timed path broken
underneath: ``correct`` holds for the sound run and fails for each fault."""
import pytest

from bench.tests import harness


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = harness.checkout(tmp_path_factory.mktemp("engine"),
                            cells=("tiny.engine",))
    return harness.load_run(root)


def _break(monkeypatch, fault):
    from repro.core.engines.lead import FlatLEADEngine
    real = FlatLEADEngine.step_with_wire

    def broken(self, state, g, key):
        new, err, bits = real(self, state, g, key)
        if fault == "unchanged":
            return state, err, bits
        if fault == "no_mix":
            return _unmixed(self, state, g, key)
        # an answer altered where it is produced: agent 0's iterate
        return new._replace(x=new.x.at[0].multiply(1.001)), err, bits

    monkeypatch.setattr(FlatLEADEngine, "step_with_wire", broken)


def _unmixed(eng, state, g, key):
    """The step with the exchange left out: the gossip stage returns each
    agent's own decoded payload as its mix."""
    hy = eng.hypers_at(state.k)
    gb = eng._blockify_g(g)
    payload, decode, bits, ctx = eng.encode_stage(state, gb, key, hy)
    q = decode(payload)
    new, err = eng.apply_stage(state, gb, q, q, hy, ctx)
    return new, err, bits


def test_sound_run_is_correct(checkout, monkeypatch):
    result = harness.drive(checkout, monkeypatch, "tiny.engine", seed=2**40)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("fault", ["unchanged", "no_mix", "altered"])
def test_a_broken_step_is_not_correct(checkout, monkeypatch, fault):
    _break(monkeypatch, fault)
    result = harness.drive(checkout, monkeypatch, "tiny.engine", seed=7)
    assert result["correct"] is False, (fault, result["checks"])


def test_the_control_is_not_correct(checkout, monkeypatch):
    """The plain reference computed in bf16 in the engine's place, as
    bench/calibrate.py runs it on the chip."""
    from bench import calibrate
    from bench.systems import engine
    control = calibrate.programs("engine", engine)["control"]
    monkeypatch.setattr(engine, "Program", control)
    result = harness.drive(checkout, monkeypatch, "tiny.engine", seed=9)
    assert result["correct"] is False, result["checks"]

"""What needs four chips, on four CPU devices in a process of their own
(bench/tests/four_devices.py): the reference spread over the devices
against the stacked one, the gossip cut planted, and whole runs of a tiny
four-chip cell, sound and broken."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import harness

WORKER = harness.ROOT / "bench" / "tests" / "four_devices.py"


@pytest.fixture(scope="module")
def four(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(WORKER), str(tmp_path_factory.mktemp("four"))],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_spread_reference_is_the_stacked_one(four):
    """Every leaf's LEAD fields on their home device, each agent's
    gradient on its own: the same numbers as every agent stacked on one
    device, to f32 round-off."""
    assert four["device_count"] == 4
    assert four["reference"]["devices"] > 1
    for name, gap in four["reference"]["gaps"].items():
        assert gap <= 1e-6, (name, gap)


def test_the_gossip_cut_mixes_each_agents_own_payload(four):
    """From the consensus start (h = hw), a step with every link down
    moves hw by the agent's own payload alone, as it moves h; a sound step
    mixes the neighbours' in."""
    cut = four["gossip_cut"]
    assert cut["gossip_cut"] <= 1e-6
    assert cut["sound"] > 1e3 * max(cut["gossip_cut"], 1e-9)


def test_a_sound_four_chip_run_is_correct(four):
    assert four["runs"]["sound"] == {"correct": True, "failing": []}


@pytest.mark.parametrize("fault", ["gossip_cut", "half_batch"])
def test_a_broken_four_chip_run_is_not_correct(four, fault):
    run = four["runs"][fault]
    assert run["correct"] is False and "change_gap" in run["failing"], run


def test_a_ring_left_still_in_the_window_is_not_correct(four):
    """The checked steps sound, every later one returning its state: the
    window's check catches it."""
    run = four["runs"]["still_in_window"]
    assert run["correct"] is False and "window_stall" in run["failing"], run

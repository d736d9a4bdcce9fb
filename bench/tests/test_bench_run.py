"""The command refuses to run without a TPU, without compiled Pallas, and
without the program; and takes a new cell and metric as data alone."""
import json
import os
import subprocess
import sys
import types

import pytest

from bench.tests import harness

ROOT = harness.ROOT
CELL = "engine.lead2.ring8.d16M"


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", "--workload",
                           CELL, "--seed", "3", "--seconds", "1", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_refuses_a_host_without_a_tpu():
    proc = _run(ROOT, "--trace", "0")
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    root = harness.checkout(tmp_path, cells=(), src=False)
    proc = _run(root, "--trace", "1")
    _no_result(proc)
    assert "no program" in proc.stderr


def test_refuses_kernels_that_are_not_compiled_pallas(monkeypatch):
    run = harness.load_run(ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    tpu = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a: [tpu])
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jnp")
    with pytest.raises(RuntimeError, match="not 'pallas'"):
        run.require_chip(1)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert run.require_chip(1)["kind"] == "TPU v5 lite"
    with pytest.raises(RuntimeError, match="needs 4 chips"):
        run.require_chip(4)


def test_a_new_cell_and_metric_are_found_by_name(tmp_path, monkeypatch):
    """A configuration, traffic, cell and per-layer metric added as new
    files, with entries appended to BENCHMARK.json, run with no existing
    file of the harness edited."""
    root = harness.checkout(tmp_path, cells=("tiny.engine",))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    (root / "bench" / "metrics" / "throwaway.engine.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['end_to_end']"
        "['engine_steps_per_s']\n")
    spec["per_layer"].append({"name": "throwaway.engine", "unit": "steps/s",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "engine_steps_per_s",
                              "workloads": ["tiny.engine"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for p, data in before.items():
        assert p.read_bytes() == data
    run = harness.load_run(root)
    result = harness.drive(run, monkeypatch, "tiny.engine")
    assert result["correct"] is True, result["checks"]
    assert set(result["metrics"]) == {"engine_steps_per_s", "setup_s"}
    cell = run.Cell(spec, "tiny.engine")
    assert [m["name"] for m in cell.per_layer][-1] == "throwaway.engine"
    ctx = {"end_to_end": {"engine_steps_per_s": 5.0}}
    assert run.load_reader("throwaway.engine").read(ctx) == 10.0
    assert list(result)[-1] == "checks"

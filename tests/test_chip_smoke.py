"""chip_smoke.py refuses to report a result anywhere but on a TPU running
compiled Pallas kernels, and the compile cache lands where the entry points
say."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_smoke(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_fails_without_a_tpu():
    r = _run_smoke(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_smoke(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_smoke_refuses_a_reference_backend_on_tpu(monkeypatch, backend):
    """A stray REPRO_KERNEL_BACKEND must not turn a chip run into a
    reference run."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTPU()])
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    with pytest.raises(RuntimeError, match="not 'pallas'"):
        chip_smoke.require_chip()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert chip_smoke.require_chip() == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_config):
    from repro.utils.compile_cache import use_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    where = use_compile_cache()
    assert where == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == where


def test_compile_cache_env_var_wins(monkeypatch, cache_config, tmp_path):
    from repro.utils.compile_cache import use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_one_chip_phases_run_on_cpu_at_tiny_size(monkeypatch):
    """The smoke's phases at toy sizes on the CPU (jnp kernels): the same
    build/loop, checks and reference comparisons the chip run makes."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    chip_smoke.phase_train(layers=2, seq_len=32, steps=3, reduced=True)
    chip_smoke.phase_engine(n=4, d=4096, steps=2, on_chip=False)
    chip_smoke.phase_simulator(n=4, d=512, m=256, iters=150)


def test_ragged_phase_runs_the_kernel_bodies_on_cpu(monkeypatch):
    """The partial-last-block check with the Pallas bodies in interpret
    mode: 300 rows are one tile of 256 and a last grid step of 44."""
    monkeypatch.syspath_prepend(str(ROOT))
    import chip_smoke
    assert chip_smoke.phase_ragged(rows=300, backend="interpret") == 0.0


def test_four_chip_phase_runs_on_four_cpu_devices():
    code = ("import chip_smoke; chip_smoke.phase_four_chips("
            "layers=2, seq_len=32, steps=2, reduced=True)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    assert "dense_w_reference_deviating_frac=" in r.stdout

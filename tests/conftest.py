# NOTE: do NOT set --xla_force_host_platform_device_count here.  Smoke tests
# and benches must see the real single device; only the subprocess-based
# distributed tests force placeholder devices.
import os
import time

import jax
import pytest


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session")
def well_posed_prob():
    """The family's well-posed (mu > 0) convergence problem: 8 agents x 64
    rows > 256 dims, so the global Hessian has full rank and quantization
    noise contracts instead of random-walking in a nullspace.  Every test
    asserting a convergence threshold should use this (or build its own
    through engine_pins.well_posed_problem, which asserts well-posedness)
    rather than an ad-hoc possibly rank-deficient LinearRegression."""
    from engine_pins import well_posed_problem
    return well_posed_problem()


# ---------------------------------------------------------------------------
# quick-lane latency budget: no single tests/test_*.py file may exceed
# REPRO_FILE_BUDGET_S seconds (default 120) of non-slow test time.  The
# budget keeps the tier-1 lane interactive — a test that belongs in the
# slow lane gets @pytest.mark.slow instead of silently inflating every
# run.  Set REPRO_FILE_BUDGET_S=0 to disable (e.g. on loaded CI workers).
# ---------------------------------------------------------------------------

_FILE_BUDGET_S = float(os.environ.get("REPRO_FILE_BUDGET_S", "120"))
_file_times = {}


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    start = time.monotonic()
    yield
    if _FILE_BUDGET_S > 0 and "slow" not in item.keywords:
        fname = str(item.fspath)
        _file_times[fname] = (_file_times.get(fname, 0.0)
                              + time.monotonic() - start)


def pytest_sessionfinish(session, exitstatus):
    if _FILE_BUDGET_S <= 0:
        return
    over = {f: t for f, t in _file_times.items() if t > _FILE_BUDGET_S}
    if over:
        lines = "\n".join(f"  {f}: {t:.1f}s" for f, t in sorted(over.items()))
        print(f"\nERROR: quick-lane file budget exceeded "
              f"({_FILE_BUDGET_S:.0f}s per test file, non-slow tests only; "
              f"REPRO_FILE_BUDGET_S overrides):\n{lines}\n"
              "Mark multi-minute cases with @pytest.mark.slow instead.")
        session.exitstatus = 1   # wrap_session returns this AFTER the hook

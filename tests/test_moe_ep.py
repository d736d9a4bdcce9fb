"""Manual expert-parallel MoE dispatch (models/moe_ep.py) vs the dense
dispatch oracle — subprocess with 8 placeholder devices."""
import os
import subprocess
import sys

import pytest

WORKER = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
from repro.models import moe as moe_mod
from repro.models import moe_ep

key = jax.random.PRNGKey(0)
d, dff, E, k = 64, 128, 8, 2
p = moe_mod.moe_init(key, d, dff, E)
x = jax.random.normal(key, (4, 16, d))
ref, _ = moe_mod.moe_apply(p, x, top_k=k, capacity_factor=8.0)
for shape in ((4, 1), (2, 4), (4, 2)):
    mesh = jax.make_mesh(shape, ("data", "model"), axis_types=(AxisType.Auto,)*2)
    with jax.set_mesh(mesh):
        px = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
        pp = {kk: jax.device_put(v, NamedSharding(mesh, P())) for kk, v in p.items()}
        for chunk in (0, 8):
            got, _ = jax.jit(lambda pp, px: moe_ep.moe_apply_ep(
                pp, px, top_k=k, capacity_factor=8.0, ep_axis="data",
                seq_chunk=chunk))(pp, px)
            err = float(jnp.max(jnp.abs(np.asarray(got) - np.asarray(ref))))
            assert err < 1e-5, (shape, chunk, err)
            # the wire is all-to-all, not all-gather/all-reduce of tokens
    comp = None
print("PASS moe_ep")
'''


@pytest.mark.slow
def test_moe_ep_matches_dense_dispatch(tmp_path):
    script = tmp_path / "w.py"
    script.write_text(WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src") \
        + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "PASS moe_ep" in r.stdout

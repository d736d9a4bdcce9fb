"""The benchmark's copies of the traffic and its plain references against
the program, on the CPU at a tiny size."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import seeds
from bench.generators import lm_stream, quadratic
from bench.reference import lead as ref_lead
from bench.reference import transformer as ref_tf

TINY = {"family": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
        "kv_heads": 2, "head_dim": 16, "d_ff": 96, "vocab": 128,
        "rope_theta": 10000.0, "tie_embeddings": False, "mlp_type": "swiglu"}


def _program_cfg(m):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", n_layers=m["n_layers"],
                       d_model=m["d_model"], n_heads=m["n_heads"],
                       kv_heads=m["kv_heads"], d_ff=m["d_ff"],
                       vocab=m["vocab"], head_dim=m["head_dim"],
                       tie_embeddings=m["tie_embeddings"])


def test_seed_keys_keep_all_64_bits():
    a, b = seeds.seed_key(5), seeds.seed_key(2 ** 33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(a), np.asarray(jax.random.PRNGKey(5)))
    with pytest.raises(ValueError):
        seeds.seed_key(2 ** 64)


def test_lm_stream_is_the_programs_lm_batch():
    from repro.data.synthetic import LMStreamConfig, lm_batch
    cfg = LMStreamConfig(vocab=512, seq_len=16, batch_per_agent=2,
                         n_agents=3, seed=0)
    gen = jax.jit(functools.partial(lm_stream.batch, vocab=512, seq_len=16,
                                    batch_per_agent=2, n_agents=3))
    for step in (0, 7):
        want, got = lm_batch(cfg, step), gen(jax.random.PRNGKey(0), step)
        for k in ("tokens", "labels"):
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]))


@pytest.mark.parametrize("tie", [True, False])
def test_weights_fit_the_programs_parameter_tree(tie):
    from repro.models import transformer as tfm
    m = dict(TINY, tie_embeddings=tie)
    want = jax.eval_shape(lambda k: tfm.init_params(_program_cfg(m), k),
                          jax.random.PRNGKey(0))
    got = ref_tf.weights_shapes(m)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert jax.tree_util.tree_map(lambda s: s.shape, got) == \
        jax.tree_util.tree_map(lambda s: s.shape, want)


@pytest.mark.parametrize("tie", [True, False])
def test_reference_model_is_the_programs_loss_and_gradient(tie):
    from repro.models import transformer as tfm
    m = dict(TINY, tie_embeddings=tie)
    cfg = _program_cfg(m)
    w = jax.jit(ref_tf.weights, static_argnums=1)(
        jax.random.PRNGKey(3), ref_tf.Frozen(m))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, 128)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(
            lambda p: tfm.loss_fn(p, cfg, batch)[0])(w)
    lr, gr = jax.value_and_grad(ref_tf.loss)(w, m, batch["tokens"],
                                             batch["labels"])
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(gr),
                    jax.tree_util.tree_leaves(gp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_reference_lead_step_is_the_engines():
    from repro.core import topology
    from repro.core.compression import QuantizePNorm
    from repro.core.engines import engine_for
    n, rows, block = 8, 16, 512
    eng = engine_for(topology.ring(n), QuantizePNorm(bits=2, block=block),
                     rows * block, gossip="neighbor", eta=0.5)
    a, b, x0 = quadratic.problem(jax.random.PRNGKey(1), agents=n, rows=rows,
                                 block=block, a_min=1.0, a_max=2.0)
    s = eng.init(x0.reshape(n, -1),
                 quadratic.grad(a, b, x0).reshape(n, -1), None)
    key = jax.random.PRNGKey(2)
    new, _, bits = eng.step_with_wire(s, quadratic.grad(a, b, s.x), key)
    W = jnp.asarray(ref_lead.ring(n), jnp.float32)
    np.testing.assert_allclose(np.asarray(W), np.asarray(eng.W), atol=1e-7)
    u = ref_lead.dither(key, n, rows, block)
    want = ref_lead.step(s.x, quadratic.grad(a, b, s.x), s.h, s.hw, s.d, u,
                         W, eta=0.5, gamma=1.0, alpha=0.5, bits=2)
    for got, ref in zip((new.x, new.h, new.hw, new.d), want):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=0, atol=1e-5)


def test_quadratic_optimum_zeroes_the_summed_gradient():
    a, b, _ = quadratic.problem(jax.random.PRNGKey(0), agents=8, rows=4,
                                block=512, a_min=1.0, a_max=2.0)
    xs = quadratic.x_star(a, b)
    g = jnp.sum(quadratic.grad(a, b, xs[None]), axis=0)
    assert float(jnp.max(jnp.abs(g))) < 1e-5


def test_leaves_go_home_by_size():
    from bench.systems import train
    assert train.homes([100, 67, 67, 67, 17, 17, 4, 4, 1], 4) == \
        [0, 1, 2, 3, 1, 2, 3, 3, 3]
    assert train.homes([5, 3, 1], 1) == [0, 0, 0]


@pytest.mark.parametrize("agents", [1, 4])
def test_the_reference_on_one_device_is_the_stacked_one(agents):
    """On one device every leaf lies there, stacked over the agents: the
    readings are those of the reference that held every agent's state so,
    exactly (the one-chip cell's numbers do not move)."""
    from bench.systems import train
    from bench.tests import harness, stacked_reference
    _, config, _, traffic, _ = harness.TINY_CELLS["tiny.train"]
    traffic = dict(traffic, agents=agents)
    key = seeds.seed_key(2 ** 35 + 1)
    got = train.reference_readings(config, traffic, key)
    want = stacked_reference.reference_readings(config, traffic, key)
    for k in ("loss", "grad_norm", "first_grad", "bits"):
        np.testing.assert_array_equal(got[k], want[k])
    for f in want["change"]:
        np.testing.assert_array_equal(got["change"][f], want["change"][f])
    for a, b in zip(jax.tree_util.tree_leaves(got["x"]),
                    jax.tree_util.tree_leaves(want["x"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""Compile-only rehearsal of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each case compiles a kernel call at a real
shape for a described (not attached) v5e, with the compiled Pallas backend
forced through ``interpret=False``, and asserts that the compiled program
holds the kernel (``tpu_custom_call``).  What the TPU compiler refuses here
(a block whose row count is neither a multiple of 8 nor the whole array, too
much VMEM) it would refuse on the chip.  The topology is described inside a
fixture, never at import, so every test worker collects the same tests and
only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import topology
from repro.core.compression import QuantizePNorm
from repro.core.engines import engine_for
from repro.dist.trainer import DistConfig, engine_of
from repro.kernels import lead_update as lu
from repro.kernels import quantize as q
from repro.kernels import sparsify
from repro.serve import kv_quant

BLOCK = 512
EMBED_ROWS = 49155 * 2048 // BLOCK       # granite-3-2b embedding: 196,620
ENGINE_ROWS = 8 * 2 ** 24 // BLOCK       # the engine phase: n=8, d=2**24


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                               # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_lead_apply_stage_at_granite_embedding(one_chip):
    """LEAD's apply stage as the trainer runs it on the embedding leaf of
    one agent: 196,620 rows, which no multiple of 8 divides."""
    eng = engine_of(DistConfig(algorithm="lead", interpret=False), 1)
    buf = _sds(one_chip, (1, EMBED_ROWS, BLOCK))
    k = _sds(one_chip, (), jnp.int32)

    def apply(x, g, h, hw, d, qh, wqh, k):
        s = eng.state_cls(x=x, h=h, hw=hw, d=d, k=k)
        return eng.apply_stage(s, g, qh, wqh, eng.hypers_at(k))[0]

    _assert_kernel(apply, *[buf] * 7, k)


def test_lead_diff_encode_at_engine_rows(one_chip):
    rows = _sds(one_chip, (ENGINE_ROWS, BLOCK))
    eta = _sds(one_chip, (), jnp.float32)
    _assert_kernel(lambda x, g, d, h, u, e: lu.lead_diff_encode(
        x, g, d, h, u, e, bits=2, interpret=False), *[rows] * 5, eta)


def test_lead_diff_encode_at_granite_embedding(one_chip):
    """The fused diff+encode kernel at 196,620 rows: a last grid step of
    12 rows, which chip_smoke.py checks against the jnp backend."""
    rows = _sds(one_chip, (EMBED_ROWS, BLOCK))
    eta = _sds(one_chip, (), jnp.float32)
    _assert_kernel(lambda x, g, d, h, u, e: lu.lead_diff_encode(
        x, g, d, h, u, e, bits=2, interpret=False), *[rows] * 5, eta)


def test_quantize_encode_decode_at_engine_rows(one_chip):
    rows = _sds(one_chip, (ENGINE_ROWS, BLOCK))
    _assert_kernel(lambda x, u: q.encode(x, u, bits=2, interpret=False),
                   rows, rows)
    _assert_kernel(lambda c, s: q.decode(c, s, bits=2, interpret=False),
                   _sds(one_chip, (ENGINE_ROWS, BLOCK), jnp.int8),
                   _sds(one_chip, (ENGINE_ROWS, 1)))


def test_engine_encode_at_padded_d2560(one_chip):
    """d=2560 is 5 blocks per agent, padded to 8: 64 rows for 8 agents, one
    whole-array tile (the old picker handed the kernel tiles of 4)."""
    eng = engine_for(topology.ring(8), QuantizePNorm(bits=2, block=BLOCK),
                     2560, interpret=False)
    buf = _sds(one_chip, (8, eng.nb, BLOCK))
    key = _sds(one_chip, (2,), jnp.uint32)
    _assert_kernel(lambda k, b: eng.encode_payload(k, b)[0], key, buf)


def test_randk_encode_at_ragged_rows(one_chip):
    rows = _sds(one_chip, (300, BLOCK))
    _assert_kernel(lambda x, u: sparsify.randk_encode(
        x, u, ratio=0.1, interpret=False), rows, rows)


def test_kv_encode_rows_with_odd_divisor(one_chip):
    """101 pages of 16 tokens x 1 kv head x 96 dims: 3 blocks of 512 per
    page, 303 rows (= 3 x 101) in all."""
    spec = kv_quant.KVQuantSpec(bits=4, block=kv_quant.pick_block(16 * 96))
    assert spec.block == BLOCK
    pages = _sds(one_chip, (101, 16, 1, 96))
    _assert_kernel(lambda x: kv_quant.encode_rows(x, spec, interpret=False),
                   pages)

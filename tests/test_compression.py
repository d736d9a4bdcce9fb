"""Compression operator tests: Assumption 2 (unbiased, C-contracted) and
Theorem 3 (p-norm variance ordering)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container image has no hypothesis
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.compression import Identity, QuantizePNorm, RandK, TopK, estimate_C


@pytest.mark.parametrize("bits", [1, 2, 4, 7])
@pytest.mark.parametrize("p", [2, np.inf])
def test_quantizer_unbiased(bits, p, key):
    q = QuantizePNorm(bits=bits, p=p, block=128)
    x = jax.random.normal(key, (512,))
    keys = jax.random.split(jax.random.PRNGKey(1), 512)
    xhats = jax.vmap(lambda k: q.compress(k, x))(keys)
    bias = jnp.mean(xhats, 0) - x
    # SE of the mean ~ scale*2^{-(b-1)}/sqrt(trials); allow 5 sigma.  The
    # quantization step is set by the *p-norm* block scale (for p=2 that is
    # the block L2 norm, much larger than max|x|), so measure it exactly.
    from repro.core.compression import _block_view, _pnorm
    blocks, _ = _block_view(x, q.block)
    scale = float(jnp.max(_pnorm(blocks.astype(jnp.float32), p)))
    tol = 5 * scale * 2.0 ** (1 - bits) / np.sqrt(512)
    assert float(jnp.max(jnp.abs(bias))) < tol


def test_quantizer_elementwise_error_bound(key):
    """|x - Q(x)| <= scale * 2^{-(b-1)} elementwise (quantization step)."""
    q = QuantizePNorm(bits=2, block=64)
    x = jax.random.normal(key, (640,))
    xh = q.compress(jax.random.PRNGKey(3), x)
    step = jnp.repeat(jnp.max(jnp.abs(x.reshape(10, 64)), 1), 64) * 0.5
    assert bool(jnp.all(jnp.abs(xh - x) <= step + 1e-6))


def test_inf_norm_lowest_variance(key):
    """Theorem 3: the compression error decreases as p increases."""
    errs = {}
    for p in (1, 2, 3, np.inf):
        q = QuantizePNorm(bits=2, p=p, block=512)
        x = jax.random.normal(key, (4096,))
        keys = jax.random.split(key, 64)
        e = jax.vmap(lambda k: jnp.sum((q.compress(k, x) - x) ** 2))(keys)
        errs[p] = float(jnp.mean(e))
    assert errs[np.inf] < errs[2] < errs[1]


def test_estimated_C_below_bound(key):
    q = QuantizePNorm(bits=2, block=512)
    C_hat = estimate_C(q, key, d=2048, trials=32)
    assert 0 < C_hat < q.variance_constant()


def test_randk_unbiased_and_C(key):
    r = RandK(ratio=0.25)
    x = jax.random.normal(key, (1024,))
    keys = jax.random.split(key, 2048)
    xh = jax.vmap(lambda k: r.compress(k, x))(keys)
    bias = jnp.mean(xh, 0) - x
    assert float(jnp.max(jnp.abs(bias))) < 0.5
    C_hat = estimate_C(r, key, d=1024, trials=32)
    assert C_hat < 1.2 * r.variance_constant() + 1.0


def test_topk_keeps_largest(key):
    t = TopK(ratio=0.1)
    x = jax.random.normal(key, (100,))
    xh = t.compress(key, x)
    kept = jnp.abs(xh) > 0
    assert int(kept.sum()) >= 10
    thresh = jnp.sort(jnp.abs(x))[-10]
    assert bool(jnp.all(jnp.abs(x)[kept] >= thresh))


def test_topk_tied_magnitudes_keep_exactly_k(key):
    """Regression: tied |x| values must not inflate the kept count past the
    k entries wire_bits charges (a `|x| >= thresh` mask keeps every tie)."""
    t = TopK(ratio=0.1)
    # 50 entries tied at |x| = 1, the rest strictly smaller: a threshold
    # mask would keep all 50; exact-k keeps 10.
    x = jnp.concatenate([jnp.ones(25), -jnp.ones(25),
                         0.5 * jnp.ones(50)])
    xh = t.compress(key, x)
    kept = int(jnp.sum(jnp.abs(xh) > 0))
    k = max(1, int(x.shape[0] * t.ratio))
    assert kept == k, (kept, k)
    assert t.wire_bits(x.shape[0]) == k * (32 + np.log2(100))
    # kept entries are all from the tied-max set
    assert bool(jnp.all(jnp.abs(xh)[jnp.abs(xh) > 0] == 1.0))

    # all-tied input, ragged k
    x2 = jnp.ones(37)
    xh2 = TopK(ratio=0.2).compress(key, x2)
    assert int(jnp.sum(jnp.abs(xh2) > 0)) == max(1, int(37 * 0.2))


def test_encode_blocks_matches_compress_rows(key):
    """Flat wire path == tree path: encode_blocks/decode_blocks over the
    blocked (n, nb, block) layout reproduce vmap'd compress() on the logical
    rows, with the shared per-agent key split."""
    n, d, block = 4, 700, 512            # ragged second block
    nb = 2
    x = jax.random.normal(key, (n, d))
    buf = jnp.pad(x, ((0, 0), (0, nb * block - d))).reshape(n, nb, block)
    from repro.core.compression import RandK, TopK as TK
    for comp in (QuantizePNorm(bits=2, block=block), RandK(ratio=0.25),
                 TK(ratio=0.1), Identity()):
        keys = jax.random.split(key, n)
        tree = jax.vmap(comp.compress)(keys, x)
        payload, bits = comp.encode_blocks(key, buf, d)
        flat = comp.decode_blocks(payload).reshape(n, -1)[:, :d]
        np.testing.assert_allclose(np.asarray(flat), np.asarray(tree),
                                   atol=1e-6, err_msg=type(comp).__name__)
        assert float(bits) > 0


@pytest.mark.parametrize("p", [np.inf, 2])
@pytest.mark.parametrize("width", [1024, 2048])
def test_encode_blocks_row_segments_match_blocks(p, width, key):
    """QuantizePNorm on (n, R, k * 512) rows — block j of a row is its lane
    segment j — gives the codes, scales and bits of the same buffer as
    (n, R * k, 512) blocks, exactly, and decodes to the same values."""
    n, R, block = 3, 40, 512
    comp = QuantizePNorm(bits=2, p=p, block=block)
    x = jax.random.normal(key, (n, R, width))
    x = x.at[0, 3].set(0.0)                    # a row of all-zero blocks
    rows_pl, rows_bits = comp.encode_blocks(key, x, R * width)
    blk = x.reshape(n, R * width // block, block)
    blk_pl, blk_bits = comp.encode_blocks(key, blk, R * width)
    k = width // block
    assert rows_pl["scale"].shape == (n, R, k)
    np.testing.assert_array_equal(np.asarray(rows_pl["code"]).reshape(blk.shape),
                                  np.asarray(blk_pl["code"]))
    np.testing.assert_array_equal(np.asarray(rows_pl["scale"]).reshape(n, -1),
                                  np.asarray(blk_pl["scale"])[..., 0])
    assert float(rows_bits) == float(blk_bits) == \
        comp.wire_bits(R * width)
    np.testing.assert_array_equal(
        np.asarray(comp.decode_blocks(rows_pl)).reshape(blk.shape),
        np.asarray(comp.decode_blocks(blk_pl)))


@pytest.mark.parametrize("shape", [(4, 2048), (3, 8192), (1, 512)])
def test_dither_rows_equal_block_draw(shape, key):
    """The threefry counter is the row-major index: a (R, C) draw is the
    (R * C / 512, 512) block matrix's draw reshaped, bit for bit — what
    lets the trainer's tiled view keep the padded path's dither."""
    R, C = shape
    for kk in jax.random.split(key, 3):
        rows = jax.random.uniform(kk, (R, C), jnp.float32)
        blocks = jax.random.uniform(kk, (R * C // 512, 512), jnp.float32)
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.asarray(blocks).reshape(R, C))


def test_identity_exact(key):
    x = jax.random.normal(key, (77,))
    assert bool(jnp.all(Identity().compress(key, x) == x))


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 2000), bits=st.integers(1, 6), seed=st.integers(0, 2**30))
def test_quantizer_roundtrip_bound_property(n, bits, seed):
    """Hypothesis: for any shape/bits, the decode error respects the
    per-block quantization-step bound."""
    q = QuantizePNorm(bits=bits, block=128)
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,))
    xh = q.compress(jax.random.PRNGKey(seed + 1), x)
    nb = -(-n // 128)
    xp = jnp.pad(x, (0, nb * 128 - n)).reshape(nb, 128)
    step = jnp.max(jnp.abs(xp), 1, keepdims=True) * 2.0 ** (1 - bits)
    bound = jnp.repeat(step, 128, 1).reshape(-1)[:n]
    assert bool(jnp.all(jnp.abs(xh - x) <= bound + 1e-6))


def test_wire_bits_accounting():
    q = QuantizePNorm(bits=2, block=512)
    assert q.wire_bits(512) == 512 * 3 + 32
    assert q.wire_bits(513) == 513 * 3 + 64
    assert Identity().wire_bits(100) == 3200


def test_topk_approx_threshold_tracks_exact(key):
    """Sampled-quantile TopK (flat path): the kept count stays near k, every
    clearly-above-threshold entry (the exact top k/2) is kept, and the kept
    values are the untouched originals — approximation only relaxes WHICH
    borderline entries make the cut, never their values."""
    n, d, block = 8, 1 << 14, 512
    nb = d // block
    x = jax.random.normal(key, (n, d))
    buf = x.reshape(n, nb, block)
    exact = TopK(ratio=0.1)
    approx = TopK(ratio=0.1, approx_threshold=True)
    k = exact._k(d)

    pl_a, bits_a = approx.encode_blocks(key, buf, d)
    vals = np.asarray(approx.decode_blocks(pl_a).reshape(n, -1)[:, :d])
    xs = np.asarray(x)

    kept = (vals != 0).sum(axis=1)
    assert np.all(kept >= 0.4 * k) and np.all(kept <= 2.5 * k), kept
    # kept entries carry their original values
    np.testing.assert_array_equal(vals[vals != 0], xs[vals != 0])
    # the unambiguous top half of the exact top-k survives the approximation
    for i in range(n):
        top_half = np.argsort(-np.abs(xs[i]))[: k // 2]
        assert np.all(vals[i][top_half] != 0)
    # bits are counted from the actual mask, not the static estimate
    assert float(bits_a) == pytest.approx(
        kept.mean() * (32 + np.log2(d)), rel=1e-6)


def test_topk_approx_zero_rows_ship_nothing(key):
    """Regression: an all-zero agent must not pay wire bits (the sampled
    threshold is 0 there; a >= 0 mask would keep the whole zero vector)."""
    n, d, block = 2, 2048, 512
    x = jnp.concatenate([jax.random.normal(key, (1, d)), jnp.zeros((1, d))])
    buf = x.reshape(n, d // block, block)
    approx = TopK(ratio=0.1, approx_threshold=True)
    pl, bits = approx.encode_blocks(key, buf, d)
    vals = approx.decode_blocks(pl).reshape(n, -1)
    assert int(jnp.sum(vals[1] != 0)) == 0
    kept0 = int(jnp.sum(vals[0] != 0))
    assert float(bits) == pytest.approx(kept0 / n * (32 + np.log2(d)),
                                        rel=1e-6)


def test_topk_approx_through_flat_engine(key):
    """The approx-threshold operator runs end to end through a flat engine
    step with finite state and positive data-dependent wire bits."""
    from repro.core import topology
    from repro.core.engines import engine_for
    from repro.core.lead import LEADHyper
    W = jnp.asarray(topology.ring(4))
    comp = TopK(ratio=0.1, approx_threshold=True)
    eng = engine_for(W, comp, 4096)
    x0 = jax.random.normal(key, (4, 4096))
    g0 = jax.random.normal(jax.random.fold_in(key, 1), (4, 4096))
    hyper = LEADHyper(eta=0.05)
    st = eng.init(x0, g0, hyper)
    st, _, bits = jax.jit(lambda s, g, k: eng.step_wire(s, g, k, hyper))(
        st, g0, key)
    assert bool(jnp.all(jnp.isfinite(st.x)))
    assert 0 < float(bits) < 4096 * 32

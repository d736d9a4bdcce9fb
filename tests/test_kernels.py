"""Pallas kernel tests: sweep shapes/dtypes, assert allclose vs the ref.py
pure-jnp oracles (interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # container image has no hypothesis
    from _hypothesis_compat import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.ops import _pick_tile, _to_blocks


@pytest.mark.parametrize("n", [64, 512, 1000, 4096, 300_000])
@pytest.mark.parametrize("bits", [1, 2, 4])
def test_encode_matches_ref(n, bits, key):
    x = jax.random.normal(jax.random.fold_in(key, n), (n,))
    code, scale = ops.quantize_encode(key, x, bits=bits, interpret=True)
    tb = _pick_tile(n, 512, 256)
    xb, _ = _to_blocks(x, 512, tb)
    u = jax.random.uniform(key, xb.shape, jnp.float32)
    rc, rs = ref.quantize_encode_ref(xb, u, bits)
    np.testing.assert_array_equal(np.asarray(code), np.asarray(rc))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(rs), rtol=1e-6)


@pytest.mark.parametrize("n", [100, 2048, 70_000])
@pytest.mark.parametrize("bits", [2, 6])
def test_decode_matches_ref(n, bits, key):
    x = jax.random.normal(jax.random.fold_in(key, n + 1), (n,))
    code, scale = ops.quantize_encode(key, x, bits=bits, interpret=True)
    got = ops.quantize_decode(code, scale, bits=bits, shape=(n,), interpret=True)
    rv = ref.quantize_decode_ref(code, scale, bits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(rv).ravel()[:n],
                               rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip_dtype_and_bound(dtype, key):
    x = jax.random.normal(key, (3000,), dtype)
    xh = ops.quantize_roundtrip(key, x, bits=2, interpret=True)
    assert xh.dtype == dtype
    xb, _ = _to_blocks(x, 512, _pick_tile(3000, 512, 256))
    step = np.repeat(np.max(np.abs(np.asarray(xb, np.float32)), 1), 512) * 0.5
    err = np.abs(np.asarray(xh, np.float32) - np.asarray(x, np.float32))
    assert np.all(err <= step[:3000] + 2e-2)


@pytest.mark.parametrize("n", [512, 7777, 131072])
def test_lead_update_matches_ref(n, key):
    arrs = [jax.random.normal(jax.random.fold_in(key, i), (n,)) for i in range(7)]
    for eta, gamma, alpha in [(0.1, 1.0, 0.5), (0.01, 0.3, 0.9)]:
        got = ops.lead_update_flat(*arrs, eta, gamma, alpha, interpret=True)
        want = ref.lead_update_ref(*arrs, eta, gamma, alpha)
        for g, w, nm in zip(got, want, ["x", "d", "h", "hw"]):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-4, err_msg=nm)


@pytest.mark.parametrize("n", [1000, 65536])
def test_lead_diff_encode_matches_composition(n, key):
    """Fused pre-comm kernel == (compute diff; encode diff) composition."""
    x, g, d, h = (jax.random.normal(jax.random.fold_in(key, i), (n,))
                  for i in range(4))
    eta = 0.07
    code, scale = ops.lead_diff_encode_flat(key, x, g, d, h, eta, bits=2,
                                            interpret=True)
    diff = x - eta * g - eta * d - h
    code2, scale2 = ops.quantize_encode(key, diff, bits=2, interpret=True)
    # same dither => identical codes (both draw uniform from the same key and
    # block layout)
    np.testing.assert_array_equal(np.asarray(code), np.asarray(code2))
    np.testing.assert_allclose(np.asarray(scale), np.asarray(scale2), rtol=1e-5)


@pytest.mark.parametrize("ratio", [0.1, 0.5])
def test_randk_encode_matches_ref(ratio, key):
    """Interpreted sparsify.randk_encode == the jnp oracle (fused in-kernel
    mask from the dither plane)."""
    from repro.kernels import sparsify
    nb, block = 4, 512
    x = jax.random.normal(key, (nb, block))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (nb, block))
    got = sparsify.randk_encode(x, u, ratio=ratio, tile_b=4, interpret=True)
    want = ref.randk_encode_ref(x, u, ratio, 1.0 / ratio)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    # unkept entries are exactly zero; kept are rescaled
    kept = np.asarray(u) < ratio
    assert np.all(np.asarray(got)[~kept] == 0.0)


def test_mask_apply_matches_ref(key):
    from repro.kernels import sparsify
    nb, block = 4, 512
    x = jax.random.normal(key, (nb, block))
    mask = (jax.random.uniform(jax.random.fold_in(key, 2),
                               (nb, block)) < 0.3).astype(jnp.float32)
    got = sparsify.mask_apply(x, mask, tile_b=4, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.mask_apply_ref(x, mask)))


@pytest.mark.parametrize("nb", [3, 6, 64])
def test_sparsify_fits_tile_to_arbitrary_row_counts(nb, key):
    """Regression: row counts that don't divide the default tile must not
    crash the non-jnp backends (callers outside the engine hand arbitrary
    nb; quantize.fit_tile takes the whole array or a ragged last block)."""
    from repro.kernels import sparsify
    block = 512
    x = jax.random.normal(key, (nb, block))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (nb, block))
    got = sparsify.randk_encode(x, u, ratio=0.3, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.randk_encode_ref(x, u, 0.3,
                                                               1 / 0.3)),
                               rtol=1e-6)
    m = (u < 0.5).astype(jnp.float32)
    got2 = sparsify.mask_apply(x, m, interpret=True)
    np.testing.assert_array_equal(np.asarray(got2),
                                  np.asarray(ref.mask_apply_ref(x, m)))


def _old_pick_tile(n_elements, block, tile_b):
    """The power-of-two tile ladder _pick_tile used before the TPU tile
    rule; the padded layouts it produced must not change."""
    nb = max(1, -(-n_elements // block))
    t = tile_b
    while t > 1 and t > nb:
        t //= 2
    return t


@pytest.mark.parametrize("picker", ["fit_tile", "pick_tile"])
def test_tile_rule_multiple_of_8_or_whole(picker):
    """Mosaic takes a block's row count only as a multiple of 8 or as the
    whole array's: both tile pickers must return one of the two (the old
    power-of-two ladders handed 2 or 4 rows to multi-tile arrays, which the
    TPU compiler refuses)."""
    from repro.kernels.quantize import fit_tile
    for n in list(range(1, 600)) + [196_620, 196_608, 262_144, 5 * 8]:
        for cap in (4, 8, 100, 256):
            if picker == "fit_tile":
                t, rows = fit_tile(n, cap), n
            else:
                t = _pick_tile(n * 512, 512, cap)
                rows = -(-n // t) * t          # the padded row count
            assert t % 8 == 0 or t == rows, (picker, n, cap, t, rows)
            assert t <= max(cap, 8) or t == rows


def test_pick_tile_keeps_padded_layout():
    """The rule changed which tile is handed to the kernels, not the
    engine's padded (nb, block) layout: nb rounds up exactly as before."""
    for n in range(1, 2000):
        new, old = _pick_tile(n * 512, 512, 256), _old_pick_tile(n * 512, 512, 256)
        assert -(-n // new) * new == -(-n // old) * old, (n, new, old)


def _pad_call(fn, rows, tile, *arrs):
    """Explicit pad-to-a-tile-multiple, call, slice back: what the ragged
    grid does implicitly."""
    pad = (-rows) % tile
    outs = fn(*[jnp.pad(a, ((0, pad), (0, 0))) for a in arrs])
    single = not isinstance(outs, (tuple, list))
    outs = [outs] if single else outs
    outs = [o[:rows] for o in outs]
    return outs[0] if single else outs


@pytest.mark.parametrize("rows", [12, 300, 1001])
def test_ragged_lead_update_matches_unpadded(rows, key):
    """A row count the tile does not divide runs as a ragged grid; every
    output row equals the single-block (unpadded) call and the explicit
    pad-and-slice call bit for bit."""
    from repro.kernels import lead_update as lu
    arrs = [jax.random.normal(jax.random.fold_in(key, i), (rows, 512))
            for i in range(7)]
    whole = lu.lead_update(*arrs, 0.1, 1.0, 0.5, tile_b=rows + 8,
                           interpret=True)
    ragged = lu.lead_update(*arrs, 0.1, 1.0, 0.5, tile_b=8, interpret=True)
    padded = _pad_call(lambda *a: lu.lead_update(*a, 0.1, 1.0, 0.5, tile_b=8,
                                                 interpret=True),
                       rows, 8, *arrs)
    for w, r, p in zip(whole, ragged, padded):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(p), np.asarray(w))


@pytest.mark.parametrize("rows", [12, 300, 1001])
def test_ragged_randk_encode_matches_unpadded(rows, key):
    from repro.kernels import sparsify
    x = jax.random.normal(key, (rows, 512))
    u = jax.random.uniform(jax.random.fold_in(key, 1), (rows, 512))
    whole = sparsify.randk_encode(x, u, ratio=0.3, tile_b=rows + 8,
                                  interpret=True)
    ragged = sparsify.randk_encode(x, u, ratio=0.3, tile_b=8, interpret=True)
    padded = _pad_call(lambda a, b: sparsify.randk_encode(
        a, b, ratio=0.3, tile_b=8, interpret=True), rows, 8, x, u)
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(padded), np.asarray(whole))


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 5000), bits=st.sampled_from([1, 2, 3, 4]),
       seed=st.integers(0, 2**29))
def test_pack_unpack_roundtrip_property(n, bits, seed):
    lo, hi = -(2 ** (bits - 1)), 2 ** (bits - 1)
    c = jax.random.randint(jax.random.PRNGKey(seed), (n,), lo, hi + 1
                           ).astype(jnp.int8)
    p = ops.pack_codes(c, bits)
    c2 = ops.unpack_codes(p, n, bits)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))
    # wire size: (bits+1) bits per element, padded to 32-bit words
    per32 = 32 // (bits + 1)
    assert p.size == -(-n // per32)


def test_kernel_vs_core_compressor_semantics(key):
    """The Pallas path and core.compression.QuantizePNorm implement the same
    quantizer (identical codes for identical dither)."""
    from repro.core.compression import QuantizePNorm
    q = QuantizePNorm(bits=2, block=512)
    x = jax.random.normal(key, (2048,))
    payload, spec = q.encode(key, x)
    # core draws uniform over the padded block matrix with the same key
    code_k, scale_k = ops.quantize_encode(key, x, bits=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(payload["code"]),
                                  np.asarray(code_k)[: payload["code"].shape[0]])

"""The yardstick's counts against hand counts, and the peak table."""
import dataclasses
import json
import pathlib

import pytest

from bench import counts

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _granite():
    return json.loads((ROOT / "bench" / "configs"
                       / "granite3-2b-L4-lead2.json").read_text())["model"]


def test_granite_counts_match_the_hand_count():
    m = _granite()
    # per layer: q 2048x2048, k and v 2048x512, o 2048x2048, SwiGLU 3x2048x8192
    layer = 2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192
    assert counts.layer_matmul_params(m) == layer == 60_817_408
    # the embedding of 49155 x 2048, which is also the head (tied, as
    # published), 4 layers with two norms each, the final norm
    assert m["tie_embeddings"] is True
    embed = 49155 * 2048
    assert counts.param_count(m) == embed + 4 * (layer + 2 * 2048) + 2048 \
        == 343_957_504
    assert len(counts.leaf_sizes(m)) == 11
    # untied, the head adds another 49155 x 2048
    untied = dict(m, tie_embeddings=False)
    assert counts.param_count(untied) == 343_957_504 + embed == 444_626_944
    assert len(counts.leaf_sizes(untied)) == 12
    # 6 (4 layers + head) + 12 L d S at S = 1024: 2.16 GFLOP per token,
    # tied or not: the head's matmul is done either way
    flops = 6 * (4 * layer + 2048 * 49155) + 12 * 4 * 2048 * 1024
    assert counts.train_flops_per_token(m, 1024) == flops == 2_164_297_728
    assert counts.train_flops_per_token(untied, 1024) == flops


@pytest.mark.parametrize("tie", [True, False])
def test_granite_counts_match_the_programs_model(tie):
    from repro.configs.registry import get_config
    m = dict(_granite(), tie_embeddings=tie)
    cfg = dataclasses.replace(get_config("granite-3-2b"),
                              n_layers=m["n_layers"], tie_embeddings=tie)
    assert cfg.param_count() == counts.param_count(m)


def test_the_configuration_ties_the_head_as_published():
    config = json.loads((ROOT / "bench" / "configs"
                         / "granite3-2b-L4-lead2.json").read_text())
    assert config["published"]["tie_word_embeddings"] is True
    assert config["model"]["tie_embeddings"] is True


def test_kernel_bytes_at_a_leaf_and_at_the_engine():
    # one stacked wq leaf: 4 x 2048 x 2048 elements, 32,768 rows of 512
    rows = counts.blocks(4 * 2048 * 2048, 512)
    assert rows == 32_768
    assert counts.lead_update_bytes(rows, 512) == 11 * 4 * 32_768 * 512
    # the engine: 8 agents x 2**24 / 512 rows
    rows = 8 * 2 ** 24 // 512
    assert counts.lead_update_bytes(rows, 512) == 5_905_580_032
    assert counts.diff_encode_bytes(rows, 512) == (
        rows * 512 * 21 + rows * 4) == 2_819_620_864
    # the embedding's 196,620 rows end in a partial block of the leaf
    assert counts.blocks(49155 * 2048, 512) == 196_620


def test_wire_bits_are_the_quantizers_meter():
    from repro.core.compression import QuantizePNorm
    q = QuantizePNorm(bits=2, block=512)
    for n in (1, 511, 512, 513, 2 ** 24, 49155 * 2048):
        assert counts.quantizer_wire_bits(n, 2, 512) == q.wire_bits(n)


def test_peaks_are_keyed_by_device_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")


def test_roofline_share_names_its_bound():
    p = counts.peaks("TPU v5 lite")
    share, bound = counts.roofline_share(819e9, 1.0, 2.0, p)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(0.0, 197e12, 1.0, p)
    assert bound == "compute" and share == pytest.approx(100.0)
    with pytest.raises(ValueError):
        counts.roofline_share(1.0, 0.0, 0.0, p)

"""What the algorithm needs, counted from shapes: the yardstick's FLOP and
byte counts and the chip's peaks.

Nothing here reads the program.  A kernel's bytes are the least its call
must move (each operand read once, each result written once), so a reading
against them cannot pass 100% of the roofline unless the time is short of
the work.
"""
from __future__ import annotations

import json
import math
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name} (known: {sorted(table)})")
    return table[device_kind]


# -- the model: a dense GQA decoder with SwiGLU, its head tied or not ------

def layer_matmul_params(m: dict) -> int:
    """Weights of one layer's matmuls: q, k, v, o and the three SwiGLU
    projections."""
    d, hd = m["d_model"], m["head_dim"]
    q, kv = m["n_heads"] * hd, m["kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"]


def param_count(m: dict) -> int:
    """Every parameter of one replica: embedding, the layers (with their two
    norms), the final norm and, where it is not tied to the embedding, the
    head."""
    return sum(leaf_sizes(m))


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token, with no recompute:
    6 x (the layers' matmul weights + the head, tied or not) for the
    matmuls, and
    12 L d S for attention's scores and values (the embedding is a gather,
    the norms and the softmax are not counted)."""
    dense = m["n_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab"]
    return 6.0 * dense + 12.0 * m["n_layers"] * m["d_model"] * seq_len


def leaf_sizes(m: dict) -> list:
    """Element count of every parameter leaf of one replica, in no
    particular order (the four layers of a kind are one stacked leaf)."""
    d, hd, L = m["d_model"], m["head_dim"], m["n_layers"]
    q, kv = m["n_heads"] * hd, m["kv_heads"] * hd
    head = [] if m["tie_embeddings"] else [d * m["vocab"]]
    return [m["vocab"] * d, d] + head + [                  # embed, norm
            L * d, L * d,                                  # ln1, ln2
            L * d * q, L * d * kv, L * d * kv, L * q * d,  # wq wk wv wo
            L * d * m["d_ff"], L * d * m["d_ff"], L * m["d_ff"] * d]


def blocks(n_elements: int, block: int) -> int:
    return -(-n_elements // block)


def quantizer_wire_bits(n_elements: int, bits: int, block: int) -> float:
    """The p=inf b-bit quantizer's payload: b bits of level and a sign bit
    per element, and one f32 scale per block of ``block`` elements."""
    return n_elements * (bits + 1) + blocks(n_elements, block) * 32


# -- the LEAD kernels, per call ---------------------------------------------

def lead_update_bytes(rows: int, block: int) -> int:
    """lead_update reads x, g, d, h, hw, q, Wq and writes x, d, h, hw:
    eleven f32 passes over the call's rows."""
    return 11 * F32 * rows * block


def diff_encode_bytes(rows: int, block: int) -> int:
    """lead_diff_encode reads x, g, d, h and the dither (f32) and writes
    one int8 code per element and one f32 scale per row."""
    return rows * block * (5 * F32 + 1) + rows * F32


def roofline_share(bytes_moved: float, flops: float, seconds: float,
                   peak: dict) -> tuple:
    """(share of the roofline in %, the bound: "memory" or "compute")."""
    t_mem = bytes_moved / peak["hbm_bytes_per_s"]
    t_flop = flops / peak["bf16_flop_per_s"]
    bound = "memory" if t_mem >= t_flop else "compute"
    if not seconds > 0 or not math.isfinite(seconds):
        raise ValueError(f"kernel time {seconds!r} is not a duration")
    return 100.0 * max(t_mem, t_flop) / seconds, bound

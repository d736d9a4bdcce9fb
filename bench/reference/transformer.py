"""A dense GQA decoder (granite-3.0 style) in plain jax.numpy, float32.

Imports nothing of the program.  The parameters are a dict whose layout is
the one the trainer stores ({embed, final_ln, [lm_head,] layers: ({ln1,
attn: {wq, wk, wv, wo}, ln2, mlp: {w_gate, w_up, w_down}},)}, the layers
stacked on a leading axis), so the benchmark's weights can be handed to
the program and to this reference alike.  Attention is the whole (S x S)
causal softmax, RoPE rotates the two halves of each head, RMSNorm has eps
1e-6 and the MLP is SwiGLU.  With ``tie_embeddings`` the head is the
embedding's transpose and there is no ``lm_head``.  Every matmul runs at
HIGHEST precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
EPS = 1e-6


class Frozen(dict):
    """A model's sizes as a hashable dict, so that jit can take them as a
    static argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _leaf_shapes(m: dict) -> dict:
    d, hd, L, V, ff = (m["d_model"], m["head_dim"], m["n_layers"],
                       m["vocab"], m["d_ff"])
    q, kv = m["n_heads"] * hd, m["kv_heads"] * hd
    layer = {"ln1": (L, d), "ln2": (L, d),
             "attn": {"wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
                      "wo": (L, q, d)},
             "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                     "w_down": (L, ff, d)}}
    head = {} if m["tie_embeddings"] else {"lm_head": (d, V)}
    return {"embed": (V, d), "final_ln": (d,), **head, "layers": (layer,)}


def _scale(path: str, shape, m: dict) -> float:
    """Init scales: 0.02 for the embedding, fan-in^-1/2 for the matmuls,
    and another (2 L)^-1/2 for the two projections into the residual."""
    if path.endswith("['embed']"):
        return 0.02
    fan_in = shape[-2]
    s = fan_in ** -0.5
    if path.endswith("['wo']") or path.endswith("['w_down']"):
        s /= (2 * m["n_layers"]) ** 0.5
    return s


def weights_shapes(m: dict):
    """jax.ShapeDtypeStruct tree of the weights."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.float32), _leaf_shapes(m),
        is_leaf=lambda s: isinstance(s, tuple) and all(
            isinstance(i, int) for i in s))


def weights(key, m: dict):
    """The model's f32 weights from ``key`` (jit this: one device call).
    Leaf i draws from fold_in(key, i) in the tree's flattening order; the
    norms' gains are ones."""
    shapes = weights_shapes(m)
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, sds) in enumerate(paths):
        name = jax.tree_util.keystr(path)
        if "ln" in name.rsplit("[", 1)[-1]:
            leaves.append(jnp.ones(sds.shape, jnp.float32))
            continue
        k = jax.random.fold_in(key, i)
        leaves.append(_scale(name, sds.shape, m)
                      * jax.random.normal(k, sds.shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rms(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * g


def _rope(x, theta: float):
    """x: (B, S, H, hd); rotate the two halves by position x frequency."""
    hd, S = x.shape[-1], x.shape[1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs      # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m, p, x):
    B, S, _ = x.shape
    nq, nkv, hd = m["n_heads"], m["kv_heads"], m["head_dim"]
    mm = functools.partial(jnp.matmul, precision=HIGHEST)
    h = _rms(x, p["ln1"])
    q = mm(h, p["attn"]["wq"]).reshape(B, S, nq, hd)
    k = mm(h, p["attn"]["wk"]).reshape(B, S, nkv, hd)
    v = mm(h, p["attn"]["wv"]).reshape(B, S, nkv, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k = jnp.repeat(k, nq // nkv, axis=2)          # query head j reads kv j//g
    v = jnp.repeat(v, nq // nkv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HIGHEST)
    x = x + mm(o.reshape(B, S, nq * hd), p["attn"]["wo"])
    h2 = _rms(x, p["ln2"])
    up = jax.nn.silu(mm(h2, p["mlp"]["w_gate"])) * mm(h2, p["mlp"]["w_up"])
    return x + mm(up, p["mlp"]["w_down"])


def loss(params, m: dict, tokens, labels):
    """Mean next-token cross-entropy over (B, S) tokens."""
    x = params["embed"][tokens]
    stacked = params["layers"][0]
    for i in range(m["n_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], stacked)
        x = jax.checkpoint(functools.partial(_layer, m))(p, x)
    h = _rms(x, params["final_ln"])
    head = (params["embed"].T if m["tie_embeddings"]
            else params["lm_head"])
    logits = jnp.matmul(h, head, precision=HIGHEST)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

"""Serving entry points: prefill and decode over a GSPMD mesh.

Serving runs ONE model (no agent stacking): params replicated over the
mesh (TP weight sharding slots into serve_param_spec when a profile needs
it), the batch dim of tokens / KV caches sharded over the "data" axis.
Each builder returns

    (fn, sds, shardings, cfg)

where `sds` are ShapeDtypeStructs for lowering without allocation and
`shardings` the matching NamedSharding pytrees — the contract the dist
tests consume.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import sharding as shr
from repro.models import transformer as tfm
from repro.serve.paged_cache import PagedKVCache


def _replicated(mesh, sds_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, P(*([None] * len(s.shape)))), sds_tree)


def _leaf_name(path) -> str:
    """Last named component of a key path ('' for unnamed, e.g. the k/v
    leaves of the contiguous KVCache which flatten positionally)."""
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return entry.name
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
    return ""


def _batched(mesh, sds_tree, batch: int):
    """Shard dim 0 over "data" for leaves whose tree position marks them as
    per-sequence state; replicate scalars and page-pool leaves.

    Classification is by key path, NOT by dimension size: a pool leaf whose
    page count happens to equal the batch (or a cache whose length equals
    it) must stay replicated — every device gathers from the whole pool.
    Leaves classified per-sequence are then required to actually lead with
    the batch dim."""
    pool = set(PagedKVCache._POOL_FIELDS)

    def one(path, s):
        if len(s.shape) == 0 or _leaf_name(path) in pool:
            return NamedSharding(mesh, P(*([None] * len(s.shape))))
        assert s.shape[0] == batch, (
            f"per-sequence cache leaf {jax.tree_util.keystr(path)} has "
            f"leading dim {s.shape[0]}, expected batch={batch}")
        return NamedSharding(mesh,
                             shr.serve_batch_spec(mesh, len(s.shape), batch))
    return jax.tree_util.tree_map_with_path(one, sds_tree)


def make_decode(cfg, mesh, prof: shr.ShardingProfile, shape):
    """Single-token decode step over a prefilled cache.

    shape: InputShape with global_batch=B and seq_len=cache length."""
    B, cache_len = shape.global_batch, shape.seq_len
    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
    cache_sds = jax.eval_shape(lambda: tfm.init_cache(cfg, B, cache_len))
    sds = {
        "params": params_sds,
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "cache": cache_sds,
    }
    shardings = {
        "params": _replicated(mesh, params_sds),
        "token": NamedSharding(mesh, shr.serve_batch_spec(mesh, 2, B)),
        "cache": _batched(mesh, cache_sds, B),
    }

    def fn(params, token, cache):
        return tfm.decode_step(params, cfg, token, cache)

    return fn, sds, shardings, cfg


def make_paged_decode(cfg, mesh, prof: shr.ShardingProfile, shape, *,
                      page: int = 16, kv_bits=None):
    """Decode step over the serving subsystem's paged cache (repro.serve).

    Same (fn, sds, shardings, cfg) contract as make_decode, but the cache
    is a paged pool + per-sequence page tables: pool leaves replicated
    (every shard gathers any page), per-sequence leaves — page_table,
    exact tails, the (B,) position/active vectors — sharded over "data"."""
    from repro.serve.paged_cache import init_paged_cache

    B, cache_len = shape.global_batch, shape.seq_len
    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
    cache_sds = jax.eval_shape(
        lambda: init_paged_cache(cfg, B, cache_len, page=page,
                                 kv_bits=kv_bits))
    sds = {
        "params": params_sds,
        "token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
        "cache": cache_sds,
    }
    shardings = {
        "params": _replicated(mesh, params_sds),
        "token": NamedSharding(mesh, shr.serve_batch_spec(mesh, 2, B)),
        "cache": _batched(mesh, cache_sds, B),
    }

    def fn(params, token, cache):
        return tfm.decode_step(params, cfg, token, cache)

    return fn, sds, shardings, cfg


def make_prefill(cfg, mesh, prof: shr.ShardingProfile, shape):
    """Full-prompt prefill: (last-token logits, populated cache)."""
    B, S = shape.global_batch, shape.seq_len
    key = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(lambda k: tfm.init_params(cfg, k), key)
    sds = {
        "params": params_sds,
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    shardings = {
        "params": _replicated(mesh, params_sds),
        "tokens": NamedSharding(mesh, shr.serve_batch_spec(mesh, 2, B)),
    }
    needs_memory = cfg.family in ("vlm", "audio")
    if needs_memory:
        M = cfg.vis_tokens if cfg.family == "vlm" else cfg.n_audio_frames
        sds["memory"] = jax.ShapeDtypeStruct((B, M, cfg.d_model), jnp.float32)
        shardings["memory"] = NamedSharding(
            mesh, shr.serve_batch_spec(mesh, 3, B))

        def fn(params, tokens, memory):
            return tfm.prefill(params, cfg, tokens, memory=memory,
                               cache_len=S)
    else:
        def fn(params, tokens):
            return tfm.prefill(params, cfg, tokens, cache_len=S)

    return fn, sds, shardings, cfg

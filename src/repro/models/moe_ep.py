"""Manual expert-parallel MoE dispatch (all-to-all), for the serving path.

Why: under pure GSPMD, the scatter-based dispatch of moe.py forces the token
batch to be *replicated* over the expert-parallel axis — every layer then
all-reduces full (T, d) activations (measured: 2.3 TB/device for kimi-k2
prefill_32k; see EXPERIMENTS.md §Perf).  This module implements the
production pattern instead, fully manual over (ep_axis, tp_axis):

  1. the f-sharded expert weights are all-gathered over TP **once per
     layer** (outside the sequence-chunk scan) — a transient ~2 GB buffer
     for kimi-k2, amortized over all chunks,
  2. route locally (partial router matmul + psum over TP: logits identical
     on every TP rank, so dispatch bookkeeping is consistent),
  3. hop-1 all-to-all over the EP axis with payloads sharded d/TP —
     each (token, choice) travels once, in the activation dtype,
  4. a Ulysses-style all-to-all over TP turns d-sharded dispatch buffers
     into token-sharded full-d blocks; each TP rank runs the FULL expert
     FFN for its token block (weights gathered in step 1 — no psum of
     activation-sized tensors anywhere),
  5. reverse transposes + hop-2 all-to-all return results to token owners.

Per-device wire per layer ~ weights/TP + chunks * (2 * k * cap * T_loc * d
/ TP) — vs the GSPMD baseline's full (T, d) f32 all-reduce per layer.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _slots(ids, n_bins, cap_slots):
    """Slot of each element within its bin (capacity-dropped beyond cap).
    Out-of-range ids get slot -1 / keep False."""
    oh = jax.nn.one_hot(ids, n_bins, dtype=jnp.int32)
    slot = (jnp.cumsum(oh, axis=0) * oh).sum(-1) - 1
    keep = (slot >= 0) & (slot < cap_slots)
    return jnp.clip(slot, 0, cap_slots - 1), keep


def _moe_ep_body(x_loc, router, wg, wu, wd, *, top_k, cap, ep_axis, tp_axis,
                 seq_chunk):
    """Fully-manual body.  Local shapes:
    x_loc (B_loc, S, d_loc)   d_loc = d / TP
    router (d_loc, E)         wg/wu (E_loc, d, f_loc)   wd (E_loc, f_loc, d)
    """
    B, S, d_loc = x_loc.shape
    nsh = jax.lax.axis_size(ep_axis)
    ntp = jax.lax.axis_size(tp_axis)
    E_loc = wg.shape[0]

    # 1. gather expert weights over TP once (amortized over all chunks).
    # The barrier ties the gathers to this layer's input: without it XLA
    # hoists all layers' (loop-invariant) gathers to the program start and
    # their buffers coexist (~316 GB for kimi-k2; see §Perf log).
    wg, wu, wd, x_loc = jax.lax.optimization_barrier((wg, wu, wd, x_loc))
    wg_f = jax.lax.all_gather(wg, tp_axis, axis=2, tiled=True)
    wu_f = jax.lax.all_gather(wu, tp_axis, axis=2, tiled=True)
    wd_f = jax.lax.all_gather(wd, tp_axis, axis=1, tiled=True)

    a2a_ep = functools.partial(jax.lax.all_to_all, axis_name=ep_axis,
                               split_axis=0, concat_axis=0, tiled=True)

    def one_chunk(x_chunk):
        Bc, Sc, _ = x_chunk.shape
        T = Bc * Sc
        xt = x_chunk.reshape(T, d_loc)

        # 2. routing (identical on all TP ranks)
        logits = jax.lax.psum(
            xt.astype(jnp.float32) @ router.astype(jnp.float32), tp_axis)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eid = jax.lax.top_k(probs, top_k)               # (T, k)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        dest = (eid // E_loc).reshape(T * top_k)
        e_in = (eid % E_loc).reshape(T * top_k)
        tok = jnp.repeat(jnp.arange(T), top_k)

        C_s = max(ntp, int(cap * T * top_k / nsh) // ntp * ntp)
        slot, keep = _slots(dest, nsh, C_s)
        send_x = jnp.zeros((nsh, C_s, d_loc), x_loc.dtype).at[dest, slot].add(
            jnp.where(keep[:, None], xt[tok], 0))
        send_e = jnp.zeros((nsh, C_s), jnp.int32).at[dest, slot].max(
            jnp.where(keep, e_in, 0))
        send_v = jnp.zeros((nsh, C_s), jnp.float32).at[dest, slot].max(
            keep.astype(jnp.float32))

        # 3. hop 1 over EP (payload d/TP-sharded)
        rx = a2a_ep(send_x).reshape(nsh * C_s, d_loc)
        re = a2a_ep(send_e).reshape(nsh * C_s)
        rv = a2a_ep(send_v).reshape(nsh * C_s)

        C_e = max(ntp, int(cap * nsh * C_s / E_loc) // ntp * ntp)
        eslot, ekeep = _slots(jnp.where(rv > 0, re, E_loc), E_loc, C_e)
        ekeep = ekeep & (rv > 0)
        buf = jnp.zeros((E_loc, C_e, d_loc), x_loc.dtype).at[re, eslot].add(
            jnp.where(ekeep[:, None], rx, 0))

        # 4. Ulysses transpose + full local FFN on my token block
        buf_t = jax.lax.all_to_all(buf, tp_axis, 1, 2, tiled=True)
        g = jnp.einsum("ecd,edf->ecf", buf_t, wg_f.astype(buf_t.dtype))
        u = jnp.einsum("ecd,edf->ecf", buf_t, wu_f.astype(buf_t.dtype))
        out_t = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u,
                           wd_f.astype(buf_t.dtype))          # (E, C/TP, d)
        out_buf = jax.lax.all_to_all(out_t, tp_axis, 2, 1, tiled=True)

        # 5. results back to token owners
        back_flat = out_buf[re, eslot] * ekeep[:, None].astype(out_buf.dtype)
        back = a2a_ep(back_flat.reshape(nsh, C_s, d_loc))

        vals = back[dest, slot] * keep[:, None].astype(back.dtype)
        w = gate.reshape(T * top_k).astype(x_loc.dtype)
        out = jnp.zeros((T, d_loc), x_loc.dtype).at[tok].add(vals * w[:, None])
        return out.reshape(Bc, Sc, d_loc)

    if seq_chunk and S > seq_chunk and S % seq_chunk == 0:
        nc = S // seq_chunk
        xc = x_loc.reshape(B, nc, seq_chunk, d_loc).swapaxes(0, 1)

        def step(_, xi):
            return None, one_chunk(xi)

        _, outs = jax.lax.scan(step, None, xc)
        return outs.swapaxes(0, 1).reshape(B, S, d_loc)
    return one_chunk(x_loc)


def moe_apply_ep(p, x, *, top_k, capacity_factor=1.25, ep_axis="data",
                 tp_axis="model", seq_chunk=0):
    """Drop-in for moe.moe_apply on the serving path (returns aux=0).

    x: (B, S, d) with B sharded over ep_axis; expert weights sharded
    P(ep_axis, ..., tp_axis).  shard_map fully manual over both axes."""
    body = functools.partial(_moe_ep_body, top_k=top_k, cap=capacity_factor,
                             ep_axis=ep_axis, tp_axis=tp_axis,
                             seq_chunk=seq_chunk)
    smapped = jax.shard_map(
        body,
        in_specs=(P(ep_axis, None, tp_axis), P(tp_axis, None),
                  P(ep_axis, None, tp_axis), P(ep_axis, None, tp_axis),
                  P(ep_axis, tp_axis, None)),
        out_specs=P(ep_axis, None, tp_axis),
        axis_names={ep_axis, tp_axis},
        check_vma=False,
    )
    out = smapped(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
    return out, jnp.zeros((), jnp.float32)

"""Subprocess worker for distributed tests (run with
XLA_FLAGS=--xla_force_host_platform_device_count=8).

Cases:
    nids_equivalence     distributed NIDS (ring ppermute) == host dense-W
                         reference (the pre-port hand-rolled NIDS math),
                         bit-for-bit up to f32 roundoff
    registry_equivalence the registry-driven trainer reproduces the
                         hand-rolled per-leaf LEAD math (dense-W host
                         reference with identical quantizer draws) step
                         for step, and its bits_per_agent metric matches
                         the quantizer's static wire accounting
    baselines_multihost  compressed baselines through the registry: CHOCO
                         trains multi-device (loss down, payload bits on
                         the wire); DeepSqueeze/EXTRA steps run and stay
                         finite
    lead_train           distributed LEAD: loss down, consensus down,
                         1^T D = 0
    dryrun_multipod      tiny (2,2,2) pod/data/model mesh: train
                         lower+compile for a reduced arch + serve decode
    perf_variants        the beyond-paper knobs (seq_parallel, wire_pack,
                         microbatches, bf16) train correctly and keep the
                         LEAD invariants
    faulted_checkpoint_resume
                         LEAD under an active FaultModel (masked gossip
                         rounds, dropped_links metric) trains finite, and
                         a kill-at-step-4 checkpoint-resume reproduces the
                         continuous run bit for bit
    leaf_layout <sub>    the trainer's tiled leaf view equals the padded
                         path bit for bit (sub: ring, wire_pack,
                         hierarchical, comm_interval, randk, choco,
                         pallas)
"""
import dataclasses
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

# Sharding-invariant threefry: with the legacy non-partitionable stream
# (default False on this jax), jit + GSPMD re-derives DIFFERENT random bits
# for a sharded operand than eager execution does, so the trainer's
# quantizer dither could never be pinned against the host dense-W references
# below.  The partitionable stream is identical under any partitioning.
jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config
from repro.data.synthetic import LMStreamConfig, lm_batch
from repro.dist import sharding as shr
from repro.dist.trainer import (DistConfig, TrainState, engine_of,
                                init_train_state, make_train_step,
                                state_shardings)
from repro.models import transformer as tfm
from repro.core import topology
from repro.utils.tree import tree_map


def _setup(algorithm, mesh_shape=(4, 2), axes=("data", "model"),
           n_agents=4, **dc_kwargs):
    mesh = jax.make_mesh(mesh_shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
    cfg = get_config("granite-3-2b").reduced()
    prof = shr.make_profile(cfg, mesh.axis_names)
    shr.set_mesh_for_rules(mesh)
    dc = DistConfig(algorithm=algorithm, **dc_kwargs)
    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    shardings = state_shardings(cfg, mesh, prof, state_sds)
    with jax.set_mesh(mesh):
        state = jax.jit(lambda k: init_train_state(cfg, mesh, prof, dc, k),
                        out_shardings=shardings)(key)
    ds = LMStreamConfig(vocab=cfg.vocab, seq_len=32, batch_per_agent=2,
                        n_agents=n_agents)
    batch = lm_batch(ds, 0)
    batch = jax.device_put(batch, NamedSharding(mesh, shr.train_batch_spec(prof)))
    return mesh, cfg, prof, dc, state, batch, key, ds


def case_nids_equivalence():
    mesh, cfg, prof, dc, state, batch, key, ds = _setup("nids")
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))

    # host reference: dense ring W on the stacked trees, same grads
    W = jnp.asarray(topology.ring(4))

    def mixT(t):
        return tree_map(lambda l: jnp.tensordot(W, l, axes=([1], [0])), t)

    grad_fn = jax.vmap(jax.grad(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    eta = engine_of(dc, 4).eta
    gamma = 1.0        # NIDS scales its dual ascent by 1/(2 eta) exactly
    x_ref = jax.device_get(state.params)
    d_ref = jax.device_get(state.algo["d"])

    with jax.set_mesh(mesh):
        for i in range(3):
            g = jax.device_get(grad_fn(jax.device_put(x_ref), batch))
            y = tree_map(lambda xl, gl, dl: xl - eta * (gl + dl), x_ref, g, d_ref)
            d_ref = tree_map(lambda dl, yl, myl: dl + gamma / (2 * eta) * (yl - myl),
                             d_ref, y, mixT(y))
            x_ref = tree_map(lambda xl, gl, dl: xl - eta * (gl + dl), x_ref, g, d_ref)
            state, _ = step(state, batch, jax.random.fold_in(key, i))

    got = jax.device_get(state.params)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(x_ref)))
    scale = max(float(jnp.max(jnp.abs(a)))
                for a in jax.tree_util.tree_leaves(x_ref))
    print("NIDS_EQUIV_ERR", err, "SCALE", scale)
    assert err < 1e-4 * max(scale, 1.0), err


def case_lead_train():
    mesh, cfg, prof, dc, state, batch, key, ds = _setup("lead")
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))

    def consensus(params):
        tot, cnt = 0.0, 0.0
        for l in jax.tree_util.tree_leaves(params):
            m = jnp.mean(l, 0, keepdims=True)
            tot += float(jnp.sum((l - m) ** 2))
            cnt += l.size
        return tot / cnt

    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state.params, batch)))
        c0 = consensus(state.params)
        for i in range(20):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, _ = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, batch)))
        c1 = consensus(state.params)
    dsum = max(float(jnp.max(jnp.abs(jnp.sum(l, 0))))
               for l in jax.tree_util.tree_leaves(state.algo["d"]))
    print("LEAD_TRAIN", l0, "->", l1, "consensus", c0, "->", c1, "dual", dsum)
    assert l1 < l0, (l0, l1)
    assert dsum < 1e-3
    assert np.isfinite(l1)


def case_dryrun_multipod():
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(AxisType.Auto,) * 3)
    cfg = get_config("granite-moe-1b-a400m").reduced()
    prof = shr.make_profile(cfg, mesh.axis_names)
    shr.set_mesh_for_rules(mesh)
    dc = DistConfig(algorithm="lead")
    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    shardings = state_shardings(cfg, mesh, prof, state_sds)
    A = 4
    batch_sds = {"tokens": jax.ShapeDtypeStruct((A, 2, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((A, 2, 64), jnp.int32)}
    bshard = {k: NamedSharding(mesh, shr.train_batch_spec(prof))
              for k in batch_sds}
    step = make_train_step(cfg, mesh, prof, dc)
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, in_shardings=(shardings, bshard, None)).lower(
            state_sds, batch_sds, jax.ShapeDtypeStruct((2,), jnp.uint32))
        compiled = lowered.compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):    # older jax: one dict per computation
        ca = ca[0]
    assert ca.get("flops", 0) > 0
    txt = compiled.as_text()
    assert "collective-permute" in txt, "ring gossip must lower to collective-permute"
    print("MULTIPOD_TRAIN_OK flops", ca.get("flops"))

    # serve decode on the multi-pod mesh
    from repro.configs.base import InputShape
    from repro.dist import serve as serve_mod
    shape = InputShape("decode_small", 128, 8, "decode")
    fn, sds, shardings2, cfg2 = serve_mod.make_decode(cfg, mesh, prof, shape)
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=(
            shardings2["params"], shardings2["token"], shardings2["cache"]),
        ).lower(sds["params"], sds["token"], sds["cache"])
        lowered.compile()
    print("MULTIPOD_DECODE_OK")


def case_perf_variants():
    """seq_parallel + wire_pack + microbatches + bf16: loss decreases and
    the dual-sum invariant holds on the optimized path too."""
    from repro.dist.trainer import DistConfig as DC
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("granite-3-2b").reduced()
    prof = shr.make_profile(cfg, mesh.axis_names)
    shr.set_mesh_for_rules(mesh)
    dc = DC(algorithm="lead", seq_parallel=True, wire_pack=True,
            microbatches=2, compute_dtype="bfloat16")
    key = jax.random.PRNGKey(0)
    state_sds = jax.eval_shape(lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
    shardings = state_shardings(cfg, mesh, prof, state_sds)
    with jax.set_mesh(mesh):
        state = jax.jit(lambda k: init_train_state(cfg, mesh, prof, dc, k),
                        out_shardings=shardings)(key)
        step = jax.jit(make_train_step(cfg, mesh, prof, dc))
        ds = LMStreamConfig(vocab=cfg.vocab, seq_len=32, batch_per_agent=2,
                            n_agents=4)
        loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
        b0 = jax.device_put(lm_batch(ds, 0),
                            NamedSharding(mesh, shr.train_batch_spec(prof)))
        l0 = float(jnp.mean(loss_fn_v(state.params, b0)))
        for i in range(12):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, _ = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, b0)))
    dsum = max(float(jnp.max(jnp.abs(jnp.sum(l, 0))))
               for l in jax.tree_util.tree_leaves(state.algo["d"]))
    print("PERF_VARIANTS", l0, "->", l1, "dual", dsum)
    assert np.isfinite(l1) and l1 < l0
    assert dsum < 5e-2  # bf16 states loosen the roundoff bound


def case_registry_equivalence():
    """Regression pin for the engine-family port: the registry-driven LEAD
    trainer must reproduce the hand-rolled per-leaf LEAD math (what
    dist/trainer.py implemented before the port) step for step.  The
    reference below is that math, written out against a dense ring W on the
    host: blockify each leaf, quantize the difference Y - H with the same
    per-leaf/per-agent key split, mix with the dense matrix, apply Alg. 1
    lines 5-7.  Subtraction order follows core/lead.py (left to right) so
    both sides feed near-bit-identical buffers into the quantizer.

    The quantizer is discontinuous, so the comparison is per-step from a
    common state: before every trainer step the TrainState is re-synced to
    the reference (the ring tests in tests/test_flat_baselines.py isolate
    the mixing the same way).  Even then a 1-ulp FP difference between the
    jitted GSPMD graph and the host graph can flip floor() on an element
    sitting exactly on a level boundary — one flipped 2-bit code moves d by
    gamma/(2 eta) * half a block scale — so the pin bounds the NUMBER of
    deviating elements (a real algebra/key/mixing bug perturbs essentially
    every element, 4+ orders of magnitude beyond the bound) and requires
    everything else to agree to 1e-4.  NIDS has its own dense-reference pin
    in case_nids_equivalence."""
    from repro.core.compression import QuantizePNorm
    from repro.dist.trainer import _leaf_blocks, _leaf_unblocks

    mesh, cfg, prof, dc, state, batch, key, ds = _setup("lead")
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    quantizer = QuantizePNorm(bits=dc.bits, block=dc.block)
    W = jnp.asarray(topology.ring(4))
    eng = engine_of(dc, 4)     # the resolved hypers the trainer actually ran
    eta, gamma, alpha = eng.eta, eng.gamma, eng.alpha
    grad_fn = jax.vmap(jax.grad(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))

    x = jax.device_get(state.params)
    h = jax.device_get(state.algo["h"])
    hw = jax.device_get(state.algo["hw"])
    d = jax.device_get(state.algo["d"])
    expect_bits = None
    total = n_bad = 0
    scale = 1.0

    with jax.set_mesh(mesh):
        for i in range(3):
            # re-sync: one-step comparison from the common reference state
            state = TrainState(params=jax.device_put(x),
                               algo={"h": jax.device_put(h),
                                     "hw": jax.device_put(hw),
                                     "d": jax.device_put(d)},
                               opt=state.opt,
                               step=jnp.asarray(i, jnp.int32))
            kk_step = jax.random.fold_in(key, i)
            g = jax.device_get(grad_fn(jax.device_put(x), batch))
            leaves_x, treedef = jax.tree_util.tree_flatten(x)
            leaves = zip(jax.random.split(kk_step, len(leaves_x)),
                         leaves_x, treedef.flatten_up_to(g),
                         treedef.flatten_up_to(h), treedef.flatten_up_to(hw),
                         treedef.flatten_up_to(d))
            nx, nh, nhw, nd, bits_sum = [], [], [], [], 0.0
            for kk, lx, lg, lh, lhw, ld in leaves:
                xb, dl = _leaf_blocks(lx, dc.block)
                gb, _ = _leaf_blocks(lg, dc.block)
                hb, _ = _leaf_blocks(lh, dc.block)
                hwb, _ = _leaf_blocks(lhw, dc.block)
                db, _ = _leaf_blocks(ld, dc.block)
                y = xb - eta * gb - eta * db
                payload, _bits = quantizer.encode_blocks(kk, y - hb, dl)
                bits_sum += quantizer.wire_bits(dl)
                qh = quantizer.decode_blocks(payload)
                wqh = jnp.tensordot(W, qh, axes=([1], [0]))
                yh, yhw = hb + qh, hwb + wqh
                hb2 = (1 - alpha) * hb + alpha * yh
                hwb2 = (1 - alpha) * hwb + alpha * yhw
                db2 = db + gamma / (2 * eta) * (yh - yhw)
                xb2 = xb - eta * gb - eta * db2
                nx.append(_leaf_unblocks(xb2, lx))
                nh.append(_leaf_unblocks(hb2, lh))
                nhw.append(_leaf_unblocks(hwb2, lhw))
                nd.append(_leaf_unblocks(db2, ld))
            x = jax.tree_util.tree_unflatten(treedef, nx)
            h = jax.tree_util.tree_unflatten(treedef, nh)
            hw = jax.tree_util.tree_unflatten(treedef, nhw)
            d = jax.tree_util.tree_unflatten(treedef, nd)
            expect_bits = bits_sum
            state, metrics = step(state, batch, kk_step)

            scale = max(scale, max(float(jnp.max(jnp.abs(a)))
                                   for a in jax.tree_util.tree_leaves(x)))
            tol = 1e-4 * scale
            for got_tree, ref_tree in ((state.params, x),
                                       (state.algo["d"], d),
                                       (state.algo["h"], h)):
                for a, b in zip(
                        jax.tree_util.tree_leaves(jax.device_get(got_tree)),
                        jax.tree_util.tree_leaves(ref_tree)):
                    dev = np.abs(np.asarray(a, np.float64)
                                 - np.asarray(b, np.float64))
                    total += dev.size
                    n_bad += int((dev > tol).sum())
            got_bits = float(metrics["bits_per_agent"])
            assert abs(got_bits - expect_bits) < 1e-3 * expect_bits, (
                got_bits, expect_bits)

    frac = n_bad / total
    print("REGISTRY_EQUIV deviating", n_bad, "/", total, f"frac {frac:.2e}",
          "scale", scale)
    assert frac < 1e-5, (n_bad, total)


def case_baselines_multihost():
    """The port's new capability: compressed baselines reach the multi-host
    path through the same registry.  CHOCO-SGD trains (loss down, actual
    payload bits reported); DeepSqueeze and EXTRA run a jitted step each
    with finite states (coverage across ErrorState / ExtraState layouts)."""
    mesh, cfg, prof, dc, state, batch, key, ds = _setup("choco")
    # tighten choco's consensus stepsize below its 0.8 paper default for
    # the 2-bit LM run (the engine default applies when gamma is omitted)
    dc = dataclasses.replace(dc, hyper={"eta": 0.03, "gamma": 0.3})
    state = init_train_state(cfg, mesh, prof, dc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state.params, batch)))
        metrics = None
        for i in range(12):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, metrics = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, batch)))
    bits = float(metrics["bits_per_agent"])
    print("CHOCO_MULTIHOST", l0, "->", l1, "bits/agent/step", bits)
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    assert bits > 0
    # a 2-bit payload must be far below the 32-bit raw size
    raw = 32 * sum(l[0].size for l in jax.tree_util.tree_leaves(state.params))
    assert bits < 0.25 * raw, (bits, raw)

    for name in ("deepsqueeze", "extra"):
        mesh, cfg, prof, dc, state, batch, key, ds = _setup(name)
        step = jax.jit(make_train_step(cfg, mesh, prof, dc))
        with jax.set_mesh(mesh):
            state, m = step(state, batch, key)
            state, m = step(state, batch, jax.random.fold_in(key, 1))
        finite = all(bool(jnp.all(jnp.isfinite(l)))
                     for l in jax.tree_util.tree_leaves(state.params))
        print("STEP_OK", name, float(m["grad_norm"]),
              float(m["bits_per_agent"]))
        assert finite, name

    # 2-agent ring: both ppermute shifts deliver the SAME neighbor, so the
    # trainer must mix with ring(2)'s (1/2, 1/2) weights, not the A >= 3
    # (1/3, 1/3)-per-shift form (regression: double-counted neighbor).
    # NIDS is deterministic, so a dense ring(2) host reference pins it.
    mesh, cfg, prof, dc, state, batch, key, ds = _setup(
        "nids", mesh_shape=(2, 4), n_agents=2)
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    W2 = jnp.asarray(topology.ring(2))

    def mixT2(t):
        return tree_map(lambda l: jnp.tensordot(W2, l, axes=([1], [0])), t)

    grad_fn = jax.vmap(jax.grad(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    eta = engine_of(dc, 2).eta
    x_ref = jax.device_get(state.params)
    d_ref = jax.device_get(state.algo["d"])
    with jax.set_mesh(mesh):
        for i in range(2):
            g = jax.device_get(grad_fn(jax.device_put(x_ref), batch))
            y = tree_map(lambda xl, gl, dl: xl - eta * gl - eta * dl,
                         x_ref, g, d_ref)
            d_ref = tree_map(lambda dl, yl, myl: dl + (yl - myl) / (2 * eta),
                             d_ref, y, mixT2(y))
            x_ref = tree_map(lambda xl, gl, dl: xl - eta * gl - eta * dl,
                             x_ref, g, d_ref)
            state, _ = step(state, batch, jax.random.fold_in(key, i))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(
                                  jax.device_get(state.params)),
                              jax.tree_util.tree_leaves(x_ref)))
    scale = max(float(jnp.max(jnp.abs(a)))
                for a in jax.tree_util.tree_leaves(x_ref))
    print("RING2_NIDS_ERR", err, "SCALE", scale)
    assert err < 1e-4 * max(scale, 1.0), err


def case_cgt_train():
    """C-GT through the multi-wire trainer path: every exchange ships TWO
    encoded payloads per leaf (iterate + tracker wires), so bits_per_agent
    must equal exactly 2x the quantizer's static single-wire accounting;
    the stored tracker invariant sum_i s_i == sum_i g_prev_i holds per
    leaf after every step (doubly stochastic ring mixing preserves column
    sums); and the loss decreases."""
    from repro.core.compression import QuantizePNorm

    mesh, cfg, prof, dc, state, batch, key, ds = _setup("cgt")
    # gradient tracking wants a smaller stepsize than the LEAD-family
    # default at this curvature (the tracker doubles the effective signal)
    dc = dataclasses.replace(dc, hyper={"eta": 0.01, "gamma": 0.3,
                                        "alpha": 0.5})
    state = init_train_state(cfg, mesh, prof, dc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state.params, batch)))
        metrics = None
        for i in range(12):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, metrics = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, batch)))

    # tracker invariant: per leaf, sum over agents of s == sum of g_prev
    inv = scale = 0.0
    for ls, lg in zip(jax.tree_util.tree_leaves(state.algo["s"]),
                      jax.tree_util.tree_leaves(state.algo["g_prev"])):
        ssum = np.asarray(jax.device_get(jnp.sum(ls, 0)), np.float64)
        gsum = np.asarray(jax.device_get(jnp.sum(lg, 0)), np.float64)
        inv = max(inv, float(np.max(np.abs(ssum - gsum))))
        scale = max(scale, float(np.max(np.abs(gsum))), 1e-6)

    # both wires metered: exactly 2x the static single-wire accounting
    quantizer = QuantizePNorm(bits=dc.bits, block=dc.block)
    expect = 2 * sum(quantizer.wire_bits(l[0].size)
                     for l in jax.tree_util.tree_leaves(state.params))
    bits = float(metrics["bits_per_agent"])
    print("CGT_MULTIHOST", l0, "->", l1, "invariant", inv, "/", scale,
          "bits", bits, "expect", expect)
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    assert inv < 1e-3 * scale, (inv, scale)
    assert abs(bits - expect) < 1e-3 * expect, (bits, expect)


def case_faulted_checkpoint_resume():
    """Fault injection on the multi-host path: LEAD trains with gossip
    rounds masked by an active FaultModel (dropped_links metric shows real
    drops, loss stays finite and decreases), and a run killed mid-training
    resumes from a checkpoint *bit-compatibly* — the fault schedule is a
    counter hash keyed on state.step, so the resumed half sees exactly the
    link drops the continuous run saw."""
    import tempfile

    from repro import checkpoint as ckpt
    from repro.core.faults import FaultModel

    fm = FaultModel(seed=11, link_drop=0.15)
    mesh, cfg, prof, dc, state0, batch, key, ds = _setup("lead", faults=fm)
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))

    def batch_at(i):
        return jax.device_put(lm_batch(ds, i),
                              NamedSharding(mesh, shr.train_batch_spec(prof)))

    dropped = 0.0
    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state0.params, batch)))
        # continuous 8-step run
        sa = state0
        for i in range(8):
            sa, m = step(sa, batch_at(i), jax.random.fold_in(key, i))
            dropped += float(m["dropped_links"])
        l1 = float(jnp.mean(loss_fn_v(sa.params, batch)))
        # the same run killed after 4 steps + checkpoint-resumed
        sb = state0
        for i in range(4):
            sb, _ = step(sb, batch_at(i), jax.random.fold_in(key, i))
        with tempfile.TemporaryDirectory() as tmp:
            ckpt.save(tmp, 4, sb)
            sb, at = ckpt.restore(tmp, sb)
            assert at == 4
        for i in range(4, 8):
            sb, _ = step(sb, batch_at(i), jax.random.fold_in(key, i))

    same = all(
        np.array_equal(np.asarray(jax.device_get(a)),
                       np.asarray(jax.device_get(b)))
        for a, b in zip(jax.tree_util.tree_leaves(sa.params),
                        jax.tree_util.tree_leaves(sb.params)))
    print("FAULT_RESUME", l0, "->", l1, "dropped", dropped, "bitcompat", same)
    assert dropped > 0, "15% link drops over 8 steps must realize some drop"
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    assert same, "checkpoint-resumed faulted run must be bit-compatible"


def case_topology_multihost():
    """The Topology API on the multi-host path: the trainer's ppermute
    schedule comes from Topology.permute_rounds(), so non-ring graphs run
    multi-device.  NIDS (deterministic) is pinned against a dense-W host
    reference on torus_2d(2, 2) (uniform weights, 3 permute rounds) and on
    an irregular erdos_renyi graph (heterogeneous metropolis weights — the
    per-receiver axis_index weight lookup); CHOCO then trains on the torus
    with compressed payloads."""
    from repro.dist.trainer import topology_of

    er4 = topology.erdos_renyi(4, p=0.5, seed=1)
    assert er4.uniform_weights is None     # irregular: exercises the
    #                                        per-receiver weight path
    for topo_cfg in ("torus", er4):
        mesh, cfg, prof, dc, state, batch, key, ds = _setup(
            "nids", topology=topo_cfg)
        topo = topology_of(dc, 4)
        W = jnp.asarray(topo.W, jnp.float32)
        step = jax.jit(make_train_step(cfg, mesh, prof, dc))

        def mixT(t, W=W):
            return tree_map(lambda l: jnp.tensordot(W, l, axes=([1], [0])), t)

        grad_fn = jax.vmap(jax.grad(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
        eta = engine_of(dc, 4).eta
        x_ref = jax.device_get(state.params)
        d_ref = jax.device_get(state.algo["d"])
        with jax.set_mesh(mesh):
            for i in range(3):
                g = jax.device_get(grad_fn(jax.device_put(x_ref), batch))
                y = tree_map(lambda xl, gl, dl: xl - eta * gl - eta * dl,
                             x_ref, g, d_ref)
                d_ref = tree_map(
                    lambda dl, yl, myl: dl + (yl - myl) / (2 * eta),
                    d_ref, y, mixT(y))
                x_ref = tree_map(lambda xl, gl, dl: xl - eta * gl - eta * dl,
                                 x_ref, g, d_ref)
                state, _ = step(state, batch, jax.random.fold_in(key, i))
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(jax.tree_util.tree_leaves(
                                      jax.device_get(state.params)),
                                  jax.tree_util.tree_leaves(x_ref)))
        scale = max(float(jnp.max(jnp.abs(a)))
                    for a in jax.tree_util.tree_leaves(x_ref))
        print("TOPOLOGY_NIDS_ERR", topo.name, err, "SCALE", scale)
        assert err < 1e-4 * max(scale, 1.0), (topo.name, err)

    # compressed algorithm on the torus: codes on the wire, loss down
    mesh, cfg, prof, dc, state, batch, key, ds = _setup(
        "choco", topology="torus")
    dc = dataclasses.replace(dc, hyper={"eta": 0.03, "gamma": 0.3})
    state = init_train_state(cfg, mesh, prof, dc, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state.params, batch)))
        for i in range(10):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, metrics = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, batch)))
    bits = float(metrics["bits_per_agent"])
    print("CHOCO_TORUS", l0, "->", l1, "bits/agent/step", bits)
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    raw = 32 * sum(l[0].size for l in jax.tree_util.tree_leaves(state.params))
    assert 0 < bits < 0.25 * raw


def case_timevarying_multihost():
    """TopologyBank through the shard_map: the trainer compiles every bank
    round's permute schedule into ONE jitted step and lax.switch(step % P)
    selects the step's graph.  DGD (deterministic, exact payload) is pinned
    against a host dense reference that mixes with W_{k % P} each step — a
    frozen graph (the pre-refactor topo(0) behavior) fails the pin from
    step 1, because the one-peer rounds are different permutations.  LEAD
    then trains on the bank (its apply_stage recomputes H_w with the step's
    graph) keeping the 1^T D = 0 invariant, and a faulted bank run drops
    only links that exist in the step's round."""
    from repro.core.faults import FaultModel

    bank = topology.exponential_onepeer(4)
    assert bank.period == 2 and bank.deg_max == 1
    mesh, cfg, prof, dc, state, batch, key, ds = _setup("dgd", topology=bank)
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    Ws = [jnp.asarray(W, jnp.float32) for W in np.asarray(bank.Ws)]
    grad_fn = jax.vmap(jax.grad(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    eta = engine_of(dc, 4).eta
    x_ref = jax.device_get(state.params)
    with jax.set_mesh(mesh):
        for i in range(4):
            g = jax.device_get(grad_fn(jax.device_put(x_ref), batch))
            W = Ws[i % bank.period]

            def mix_step(xl, gl, W=W):
                return jnp.tensordot(W, xl, axes=([1], [0])) - eta * gl

            x_ref = tree_map(mix_step, x_ref, g)
            state, _ = step(state, batch, jax.random.fold_in(key, i))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(jax.tree_util.tree_leaves(
                                  jax.device_get(state.params)),
                              jax.tree_util.tree_leaves(x_ref)))
    scale = max(float(jnp.max(jnp.abs(a)))
                for a in jax.tree_util.tree_leaves(x_ref))
    print("BANK_DGD_ERR", err, "SCALE", scale)
    assert err < 1e-4 * max(scale, 1.0), err

    # LEAD on the bank: compressed payloads over the round graphs, H_w
    # recomputed per step — finite, loss down, dual sum zero
    mesh, cfg, prof, dc, state, batch, key, ds = _setup("lead", topology=bank)
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    loss_fn_v = jax.jit(jax.vmap(lambda p, b: tfm.loss_fn(p, cfg, b)[0]))
    with jax.set_mesh(mesh):
        l0 = float(jnp.mean(loss_fn_v(state.params, batch)))
        for i in range(8):
            b = jax.device_put(lm_batch(ds, i),
                               NamedSharding(mesh, shr.train_batch_spec(prof)))
            state, metrics = step(state, b, jax.random.fold_in(key, i))
        l1 = float(jnp.mean(loss_fn_v(state.params, batch)))
    dsum = max(float(jnp.max(jnp.abs(jnp.sum(l, 0))))
               for l in jax.tree_util.tree_leaves(state.algo["d"]))
    bits = float(metrics["bits_per_agent"])
    print("BANK_LEAD", l0, "->", l1, "dual", dsum, "bits", bits)
    assert np.isfinite(l1) and l1 < l0, (l0, l1)
    assert dsum < 1e-3, dsum
    raw = 32 * sum(l[0].size for l in jax.tree_util.tree_leaves(state.params))
    assert 0 < bits < 0.25 * raw

    # faulted bank run: the link masks compose with the step's round graph
    fm = FaultModel(seed=5, link_drop=0.3)
    mesh, cfg, prof, dc, state, batch, key, ds = _setup(
        "lead", topology=bank, faults=fm)
    step = jax.jit(make_train_step(cfg, mesh, prof, dc))
    dropped = 0.0
    with jax.set_mesh(mesh):
        for i in range(6):
            state, m = step(state, batch, jax.random.fold_in(key, i))
            d_i = float(m["dropped_links"])
            # deg-1 rounds: at most ONE directed link per agent per step
            assert 0 <= d_i <= 4, d_i
            dropped += d_i
    finite = all(bool(jnp.all(jnp.isfinite(l)))
                 for l in jax.tree_util.tree_leaves(state.params))
    print("BANK_FAULTED dropped", dropped, "finite", finite)
    assert dropped > 0 and finite


def case_leaf_layout():
    """The trainer's tiled leaf view against the padded path, bit for bit,
    for the sub-case named by the next argument.

    A small LEAD model on 4 agents whose leaves have last dims of 512 (the
    embedding, wq, wo, w_down) and 1024 (w_gate, w_up), two layers stacked
    in each, 1-D norms, and kv projections of 128 lanes and an untied head
    of 256, which no block of 512 divides (the padded path).  The sub-case
    runs 3 steps twice from one state, batches and keys: as the trainer
    takes each leaf, and with the view selector patched to always take the
    padded path.  "pallas" runs the Pallas kernels in interpret mode, so the
    quantize kernel encodes and decodes inside the trainer's shard_map on
    each device's own agent.  Prints one LEAF_LAYOUT line:
    the fields (params, the engine's state fields, bits_per_agent) that
    differ, both runs' layout counts and the bits of each step."""
    import json

    from repro.core.compression import RandK
    from repro.dist import trainer as tr

    cfg = dataclasses.replace(
        get_config("granite-3-2b").reduced(d_model=512, vocab=256),
        kv_heads=1, head_dim=128, d_ff=1024)
    flat = ((4, 1), ("data", "model"))
    (mesh_shape, axes), dc_kwargs = {
        "ring": (flat, {}),
        "wire_pack": (flat, {"wire_pack": True}),
        "hierarchical": (((2, 2), ("pod", "data")),
                         {"topology": topology.hierarchical(
                             topology.ring(2), 2)}),
        "comm_interval": (flat, {"topology": topology.ring(4)
                                 .with_interval(2)}),
        "randk": (flat, {"compressor": RandK(ratio=0.25)}),
        "pallas": (flat, {"interpret": True}),
        "choco": (flat, {"algorithm": "choco"}),
    }[sys.argv[2]]

    def run():
        mesh = jax.make_mesh(mesh_shape, axes,
                             axis_types=(AxisType.Auto,) * len(axes),
                             devices=jax.devices()[:4])
        prof = shr.make_profile(cfg, mesh.axis_names)
        shr.set_mesh_for_rules(mesh)
        dc = DistConfig(**{"algorithm": "lead", **dc_kwargs})
        key = jax.random.PRNGKey(0)
        sds = jax.eval_shape(
            lambda k: init_train_state(cfg, mesh, prof, dc, k), key)
        shardings = state_shardings(cfg, mesh, prof, sds)
        ds = LMStreamConfig(vocab=cfg.vocab, seq_len=16, batch_per_agent=2,
                            n_agents=4)
        bspec = NamedSharding(mesh, shr.train_batch_spec(prof))
        with jax.set_mesh(mesh):
            state = jax.jit(lambda k: init_train_state(cfg, mesh, prof, dc, k),
                            out_shardings=shardings)(key)
            fn = make_train_step(cfg, mesh, prof, dc)
            step = jax.jit(fn)
            bits = []
            for i in range(3):
                b = jax.device_put(lm_batch(ds, i), bspec)
                state, m = step(state, b, jax.random.fold_in(key, i))
                bits.append(np.asarray(m["bits_per_agent"]))
        out = {"params": state.params, **state.algo, "bits_per_agent": bits}
        return jax.device_get(out), fn.layout

    tiled, layout = run()
    view = tr._leaf_view
    tr._leaf_view = lambda *a, **k: None
    try:
        padded, padded_layout = run()
    finally:
        tr._leaf_view = view
    pairs = {f: list(zip(jax.tree_util.tree_leaves(tiled[f]),
                         jax.tree_util.tree_leaves(padded[f])))
             for f in tiled}
    differ = [f for f, ab in pairs.items()
              if not all(np.array_equal(a, b) for a, b in ab)]
    # elements of the state off by more than 1e-4 of the largest parameter
    tol = 1e-4 * max(float(np.max(np.abs(a)))
                     for a, _ in pairs["params"])
    deviating = sum(int(np.sum(np.abs(np.asarray(a, np.float64)
                                      - np.asarray(b, np.float64)) > tol))
                    for f, ab in pairs.items() if f != "bits_per_agent"
                    for a, b in ab)
    elements = sum(np.size(a) for f, ab in pairs.items()
                   if f != "bits_per_agent" for a, _ in ab)
    print("LEAF_LAYOUT", json.dumps({
        "differ": differ, "fields": sorted(tiled), "layout": layout,
        "padded_layout": padded_layout, "deviating": deviating,
        "elements": elements,
        "bits": [float(b) for b in tiled["bits_per_agent"]],
        "padded_bits": [float(b) for b in padded["bits_per_agent"]]}))

if __name__ == "__main__":
    case = sys.argv[1]
    {"nids_equivalence": case_nids_equivalence,
     "registry_equivalence": case_registry_equivalence,
     "baselines_multihost": case_baselines_multihost,
     "lead_train": case_lead_train,
     "dryrun_multipod": case_dryrun_multipod,
     "perf_variants": case_perf_variants,
     "cgt_train": case_cgt_train,
     "faulted_checkpoint_resume": case_faulted_checkpoint_resume,
     "topology_multihost": case_topology_multihost,
     "timevarying_multihost": case_timevarying_multihost,
     "leaf_layout": case_leaf_layout}[case]()
    print("PASS", case)

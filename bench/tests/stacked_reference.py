"""The training reference as it ran with every agent's state stacked on one
device: the yardstick that bench/systems/train.py ``reference_readings``,
which spreads the leaves over the chips, is held to in the tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import counts
from bench.reference import lead as ref_lead
from bench.reference import transformer as ref_tf
from bench.systems.train import (CHECKED_STEPS, _keys, _ref_grad,
                                 _ref_leaf_step, generator, step_key)


def reference_readings(config: dict, traffic: dict, seed_key) -> dict:
    m = ref_tf.Frozen(config["model"])
    alg = config["algorithm"]
    A = traffic["agents"]
    W = jnp.asarray(ref_lead.ring(A), jnp.float32)
    eta, gamma, alpha = (float(alg[k]) for k in ("eta", "gamma", "alpha"))
    wkey, dkey, skey = _keys(seed_key)
    gen = jax.jit(generator(traffic, m["vocab"], A))
    w = jax.jit(ref_tf.weights, static_argnums=1)(wkey, m)
    stack = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l[None], (A,) + l.shape), t))
    x = stack(w)
    h = jax.tree_util.tree_map(jnp.copy, x)
    hw = jax.tree_util.tree_map(jnp.copy, x)
    d = jax.tree_util.tree_map(jnp.zeros_like, x)
    out = {"loss": [], "grad_norm": [], "bits": []}
    wire = sum(counts.quantizer_wire_bits(n, alg["bits"], alg["block"])
               for n in (int(np.prod(l.shape[1:]))
                         for l in jax.tree_util.tree_leaves(x)))
    for k in range(CHECKED_STEPS):
        b = gen(dkey, k)
        losses, grads = [], []
        for a in range(A):
            loss, g = _ref_grad(x, b["tokens"], b["labels"], m=m, agent=a)
            losses.append(float(loss))
            grads.append(g)
        g = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *grads)
        gl = jax.tree_util.tree_leaves(g)
        per = np.stack([np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            l.reshape(A, -1)), 1))) for l in gl])
        out["loss"].append(np.asarray(losses))
        out["grad_norm"].append(float(np.sqrt(np.sum(per.astype(np.float64)
                                                      ** 2))))
        out["bits"].append(float(wire))
        if k == 0:
            out["first_grad"] = per
        leaves, treedef = jax.tree_util.tree_flatten(x)
        keys = jax.random.split(step_key(skey, k), len(leaves))
        fields = [jax.tree_util.tree_leaves(t) for t in (x, h, hw, d)]
        new = [[], [], [], []]
        for i in range(len(leaves)):
            r = _ref_leaf_step(fields[0][i], gl[i], fields[1][i],
                               fields[2][i], fields[3][i], keys[i], W,
                               bits=alg["bits"], block=alg["block"],
                               eta=eta, gamma=gamma, alpha=alpha)
            for j in range(4):
                new[j].append(r[j])
        x, h, hw, d = (jax.tree_util.tree_unflatten(treedef, f) for f in new)
    x0 = stack(w)

    def norms(f, base):
        return np.stack([np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            (a - b).reshape(A, -1)), 1))) for a, b in zip(
            jax.tree_util.tree_leaves(f), jax.tree_util.tree_leaves(base))])

    zero = jax.tree_util.tree_map(jnp.zeros_like, x0)
    out["change"] = {"x": norms(x, x0), "h": norms(h, x0),
                     "hw": norms(hw, x0), "d": norms(d, zero)}
    out["loss"] = np.stack(out["loss"])
    out["x"] = x
    return out

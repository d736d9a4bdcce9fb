"""Language-model traffic: heterogeneous per-agent token streams.

A copy of the program's synthetic ``lm_batch`` (data/synthetic.py), keyed
by the run's seed instead of a fixed one: with probability 0.8 a token
comes from the agent's own block of ``block_size`` preferred ids, else it
is uniform over the vocabulary, so agents' gradients disagree.  Batch
``step`` is a pure function of (key, step), so every step's rows differ and
the same seed gives the same stream.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batch(key, step, *, vocab: int, seq_len: int, batch_per_agent: int,
          n_agents: int, block_size: int = 64):
    """{tokens, labels}: (n_agents, batch_per_agent, seq_len) int32, labels
    the next token.  Jit it with the sizes bound (functools.partial)."""
    def one(a):
        k = jax.random.fold_in(jax.random.fold_in(key, step), a)
        k1, k2, k3 = jax.random.split(k, 3)
        shape = (batch_per_agent, seq_len + 1)
        uniform = jax.random.randint(k1, shape, 0, vocab)
        lo = (a * block_size) % max(vocab - block_size, 1)
        pref = lo + jax.random.randint(k2, shape, 0, block_size)
        toks = jnp.where(jax.random.bernoulli(k3, 0.8, shape), pref, uniform)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    return jax.vmap(one)(jnp.arange(n_agents))

"""The flat engine's gradient oracle: a heterogeneous separable quadratic.

Agent i holds f_i(x) = a_i / 2 ||x - b_i||^2 with a scalar a_i drawn
uniformly from [a_min, a_max] and a vector b_i ~ N(0, 1); its gradient is
a_i (x - b_i), and the sum's minimiser is x* = sum_i a_i b_i / sum_i a_i.
The start x_i^0 ~ N(0, 1) differs per agent.  Everything is drawn from the
run's key in the engine's (agents, rows, block) layout, on the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("agents", "rows", "block",
                                             "a_min", "a_max"))
def problem(key, *, agents: int, rows: int, block: int, a_min: float,
            a_max: float):
    """(a (agents, 1, 1), b, x0 (agents, rows, block))."""
    ka, kb, kx = jax.random.split(key, 3)
    a = jax.random.uniform(ka, (agents, 1, 1), jnp.float32, a_min, a_max)
    b = jax.random.normal(kb, (agents, rows, block), jnp.float32)
    x0 = jax.random.normal(kx, (agents, rows, block), jnp.float32)
    return a, b, x0


def grad(a, b, x):
    return a * (x - b)


def x_star(a, b):
    return jnp.sum(a * b, axis=0) / jnp.sum(a, axis=0)


def step_key(key, k):
    """The key of iteration k: the same stream for program and reference."""
    return jax.random.fold_in(jax.random.fold_in(key, 1), k)

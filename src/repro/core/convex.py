"""Convex objectives from the paper's experiments (§5) + closed-form optima.

* Linear regression:  f_i(x) = ||A_i x - b_i||^2 + lambda ||x||^2
  (paper: A_i in R^{200x200}, b_i = A_i x' + noise, lambda = 0.1).
* Logistic regression: multinomial LR with l2 regularization on a synthetic
  10-class Gaussian-mixture dataset (MNIST is not available offline; dims are
  matched: d=784, 10 classes).  Homogeneous = shuffled partition;
  heterogeneous = label-sorted partition (paper §5).

All objectives expose:
    full_grad(X)            (n, d)->(n, d)   per-agent full-batch gradients
    minibatch_grad(X, key)  stochastic gradients (paper's mini-batch setting)
    loss(X)                 mean of local losses at the agent-local iterates
    x_star                  the global optimizer (closed form / Newton)
    mu, L                   strong-convexity / smoothness constants
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# The objectives' own arithmetic runs at full f32 precision: at a TPU's
# default one-pass bf16 matmul the gradient at x* is a rounding error
# away from zero, and the optimum the algorithms reach moves with it.
HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LinearRegression:
    A: jnp.ndarray        # (n, m, d)
    b: jnp.ndarray        # (n, m)
    lam: float

    @staticmethod
    def generate(key, n_agents=8, m=200, d=200, lam=0.1, noise=0.1):
        k1, k2, k3 = jax.random.split(key, 3)
        A = jax.random.normal(k1, (n_agents, m, d)) / jnp.sqrt(m)
        x_true = jax.random.normal(k2, (d,))
        b = (jnp.einsum("nmd,d->nm", A, x_true, precision=HIGHEST)
             + noise * jax.random.normal(k3, (n_agents, m)))
        return LinearRegression(A=A, b=b, lam=lam)

    @property
    def n(self):
        return self.A.shape[0]

    @property
    def d(self):
        return self.A.shape[2]

    def local_grad(self, i, x):
        Ai, bi = self.A[i], self.b[i]
        r = jnp.matmul(Ai, x, precision=HIGHEST) - bi
        return (2.0 * jnp.matmul(Ai.T, r, precision=HIGHEST)
                + 2.0 * self.lam * x)

    def full_grad(self, X):
        """X: (n, d) -> per-agent gradients (n, d)."""
        r = jnp.einsum("nmd,nd->nm", self.A, X, precision=HIGHEST) - self.b
        return (2.0 * jnp.einsum("nmd,nm->nd", self.A, r, precision=HIGHEST)
                + 2.0 * self.lam * X)

    def minibatch_grad(self, X, key, batch=32):
        n, m, d = self.A.shape
        idx = jax.random.randint(key, (n, batch), 0, m)
        Ab = jax.vmap(lambda a, i: a[i])(self.A, idx)          # (n, batch, d)
        bb = jax.vmap(lambda b, i: b[i])(self.b, idx)          # (n, batch)
        r = jnp.einsum("nmd,nd->nm", Ab, X, precision=HIGHEST) - bb
        return (2.0 * (m / batch)
                * jnp.einsum("nmd,nm->nd", Ab, r, precision=HIGHEST)
                + 2.0 * self.lam * X)

    def loss(self, X):
        r = jnp.einsum("nmd,nd->nm", self.A, X, precision=HIGHEST) - self.b
        return jnp.mean(jnp.sum(r ** 2, -1) + self.lam * jnp.sum(X ** 2, -1))

    @property
    def x_star(self) -> jnp.ndarray:
        """Closed form: x* = (sum 2 A_i^T A_i + 2 n lam I)^{-1} sum 2 A_i^T b_i."""
        H = (2.0 * jnp.einsum("nmd,nme->de", self.A, self.A, precision=HIGHEST)
             + 2.0 * self.n * self.lam * jnp.eye(self.d))
        g = 2.0 * jnp.einsum("nmd,nm->d", self.A, self.b, precision=HIGHEST)
        return jnp.linalg.solve(H, g)

    @property
    def mu_L(self):
        """Assumption 4 constants: EACH f_i is L-smooth / mu-strongly convex,
        so mu = min_i lambda_min(H_i), L = max_i lambda_max(H_i)."""
        H = (2.0 * jnp.einsum("nmd,nme->nde", self.A, self.A, precision=HIGHEST)
             + 2.0 * self.lam * jnp.eye(self.d)[None])
        ev = jnp.linalg.eigvalsh(H)                     # (n, d)
        return float(jnp.min(ev[:, 0])), float(jnp.max(ev[:, -1]))


@dataclasses.dataclass(frozen=True)
class LogisticRegression:
    """Multinomial logistic regression, one data shard per agent."""
    feats: jnp.ndarray     # (n, m, d)
    labels: jnp.ndarray    # (n, m) int
    n_classes: int
    lam: float

    @staticmethod
    def generate(key, n_agents=8, m_per_agent=256, d=784, n_classes=10,
                 lam=1e-4, heterogeneous=True, sep=3.0):
        """Gaussian-mixture surrogate for MNIST.  heterogeneous=True sorts by
        label before partitioning (paper's heterogeneous setting)."""
        k1, k2, k3 = jax.random.split(key, 3)
        total = n_agents * m_per_agent
        centers = sep * jax.random.normal(k1, (n_classes, d)) / jnp.sqrt(d)
        y = jax.random.randint(k2, (total,), 0, n_classes)
        xfeat = centers[y] + jax.random.normal(k3, (total, d)) / jnp.sqrt(d)
        if heterogeneous:
            order = jnp.argsort(y)
        else:
            order = jax.random.permutation(jax.random.fold_in(key, 7), total)
        xfeat, y = xfeat[order], y[order]
        feats = xfeat.reshape(n_agents, m_per_agent, d)
        labels = y.reshape(n_agents, m_per_agent)
        return LogisticRegression(feats=feats, labels=labels,
                                  n_classes=n_classes, lam=lam)

    @property
    def n(self):
        return self.feats.shape[0]

    @property
    def d(self):
        """Flattened parameter dimension (d_features * n_classes)."""
        return self.feats.shape[2] * self.n_classes

    def _unflatten(self, X):
        n = X.shape[0]
        return X.reshape(n, self.feats.shape[2], self.n_classes)

    def _loss_one(self, w, feats, labels):
        logits = jnp.matmul(feats, w, precision=HIGHEST)     # (m, c)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
        return nll + 0.5 * self.lam * jnp.sum(w ** 2)

    def full_grad(self, X):
        W = self._unflatten(X)
        g = jax.vmap(jax.grad(self._loss_one))(W, self.feats, self.labels)
        return g.reshape(X.shape)

    def minibatch_grad(self, X, key, batch=64):
        n, m, _ = self.feats.shape
        idx = jax.random.randint(key, (n, batch), 0, m)
        fb = jax.vmap(lambda f, i: f[i])(self.feats, idx)
        lb = jax.vmap(lambda l, i: l[i])(self.labels, idx)
        W = self._unflatten(X)
        g = jax.vmap(jax.grad(self._loss_one))(W, fb, lb)
        return g.reshape(X.shape)

    def loss(self, X):
        W = self._unflatten(X)
        return jnp.mean(jax.vmap(self._loss_one)(W, self.feats, self.labels))

    def solve_x_star(self, iters=500) -> jnp.ndarray:
        """Global optimum by full-batch gradient descent on the average
        objective (strongly convex => unique)."""
        d = self.d

        def avg_loss(w):
            X = jnp.broadcast_to(w[None], (self.n, d))
            return self.loss(X)

        w = jnp.zeros((d,))
        g_fn = jax.jit(jax.grad(avg_loss))

        # crude Lipschitz estimate for the stepsize
        L = float(jnp.mean(jnp.sum(self.feats ** 2, -1))) + self.lam
        lr = 1.0 / L

        def body(w, _):
            return w - lr * g_fn(w), None

        w, _ = jax.lax.scan(body, w, None, length=iters)
        return w


# -- metrics -----------------------------------------------------------------

def distance_to_opt(X, x_star):
    """(1/n) sum_i ||x_i - x*||^2   (paper Fig. 1a / 2a)."""
    return jnp.mean(jnp.sum((X - x_star[None]) ** 2, -1))


def consensus_error(X):
    """(1/n) sum_i ||x_i - xbar||^2   (paper Fig. 1c / Corollary 2)."""
    xbar = jnp.mean(X, 0, keepdims=True)
    return jnp.mean(jnp.sum((X - xbar) ** 2, -1))

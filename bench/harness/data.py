"""Inputs made on the device from ``--seed``, in one jitted call each.

The data is a class-structured stand-in with the geometry of the paper's
Table 1 (no dataset files exist offline): each class is a mixture of
anisotropic Gaussians on a random low-dimensional manifold, squashed to
[0, 1], as the program's synthetic-data mixture makes it. The mixture itself
(the manifold, the modes' centres and scales) is one fixed distribution, as
a dataset is; the seed draws the rows from it. So every seed trains and
serves the same kind of data, and a seed changes which rows, not how much
work they make.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: Key of the fixed mixture: the stand-in dataset's identity.
DATASET_KEY = 0xD47A5E7


def root_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed up to 64 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "dim", "classes", "modes"))
def _mixture(key, *, n: int, dim: int, classes: int, modes: int = 3):
    manifold = max(4, dim // 8)
    k_proj, k_mu, k_scale = jax.random.split(jax.random.PRNGKey(DATASET_KEY),
                                             3)
    k_cls, k_mode, k_eps = jax.random.split(key, 3)
    m = classes * modes
    proj = jax.random.normal(k_proj, (manifold, dim)) / jnp.sqrt(manifold)
    mu = 2.0 * jax.random.normal(k_mu, (m, manifold))
    scale = 0.25 + 0.5 * jax.random.uniform(k_scale, (m, manifold))
    cls = jax.random.randint(k_cls, (n,), 0, classes)
    mode = cls * modes + jax.random.randint(k_mode, (n,), 0, modes)
    z = mu[mode] + scale[mode] * jax.random.normal(k_eps, (n, manifold))
    x = jax.nn.sigmoid(jnp.matmul(z, proj, precision="highest"))
    return x.astype(jnp.float32), cls.astype(jnp.int32)


def make_data(key: jax.Array, data_cfg: dict):
    """(x_train, y_train, x_test, y_test) on the device: rows drawn from the
    seed, train and test from the one fixed mixture."""
    n_tr, n_te = int(data_cfg["train"]), int(data_cfg["test"])
    x, y = _mixture(jax.random.fold_in(key, 0xDA7A), n=n_tr + n_te,
                    dim=int(data_cfg["dim"]),
                    classes=int(data_cfg["classes"]))
    return x[:n_tr], y[:n_tr], x[n_tr:], y[n_tr:]

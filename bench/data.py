"""Inputs and weights of a cell, made on the device from ``--seed``.

The rows follow the program's simulation of the paper's datasets (a
gaussian mixture with ``clusters_per_class`` clusters per class whose
centres lie ``margin`` apart; arithmetic copied from
``repro.data.synthetic.make_classification``). The mixture's geometry (its
centres) is part of the deployment and comes from the configuration's
``geometry_seed``; ``--seed`` draws the rows, the labels, the basis and the
weights. So every seed asks the same problem of the solver, on other rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A PRNG key for any whole number, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _rows(geometry_key, key, *, n, d, clusters, margin):
    centres = jax.random.normal(geometry_key, (clusters, d)) * margin
    kc, kx = jax.random.split(key)
    cls = jax.random.randint(kc, (n,), 0, clusters)
    x = centres[cls] + jax.random.normal(kx, (n, d)) * (margin * 0.6 + 0.2)
    y = jnp.where(cls % 2 == 0, 1.0, -1.0).astype(jnp.float32)
    return x, y


def rows(cfg: dict, seed: int, part: str, n: int, shardings=None):
    """(X, y) for ``part`` ("train" or "test") of the configuration's
    dataset, made in one jitted call on the device (or straight into
    ``shardings``, a pair of shardings for X and y)."""
    mix = cfg["mixture"]
    fn = jax.jit(_rows, static_argnames=("n", "d", "clusters", "margin"),
                 out_shardings=shardings)
    return fn(jax.random.PRNGKey(mix["geometry_seed"]),
              jax.random.fold_in(key_of(seed), {"train": 0, "test": 1}[part]),
              n=n, d=cfg["d"], clusters=2 * mix["clusters_per_class"],
              margin=float(mix["margin"]))


@functools.partial(jax.jit, static_argnames=("m",))
def basis_of(key, X, *, m):
    """m distinct rows of X, drawn from ``key`` (the random basis)."""
    idx = jax.random.choice(key, X.shape[0], (m,), replace=False)
    return X[idx]


def basis(cfg: dict, seed: int, X):
    return basis_of(jax.random.fold_in(key_of(seed), 2), X, m=cfg["m"])


@functools.partial(jax.jit, static_argnames=("m",))
def _weights(key, *, m):
    return 0.1 * jax.random.normal(key, (m,), jnp.float32)


def weights(cfg: dict, seed: int):
    """Seeded serving weights beta (m,): serving time does not depend on
    how far beta was trained, and the reference may take nothing that the
    program has made."""
    return _weights(jax.random.fold_in(key_of(seed), 3), m=cfg["m"])


def host_rng(seed: int) -> np.random.Generator:
    """A numpy generator for host-side draws (request sizes, rows, order)."""
    return np.random.default_rng(int(seed))

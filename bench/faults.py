"""Faults planted under the timed path, to show that ``correct`` catches
them. Each is a context manager that patches the program while it is
active; the tests run cells under them on the CPU, and
``bench/calibrate.py --fault`` reads them on the chip.

- ``unchanged``: the fit returns its state unchanged (beta = beta0).
- ``half_batch``: the fit sees only the first half of the rows, each
  counted twice (the mean taken over the rest); shapes are unchanged.
- ``no_exchange``: the psum between chips is left out.
- ``altered_answer``: the first margin of every served batch is altered.
- ``half_served``: a served batch computes only its first half of rows.
"""
from __future__ import annotations

import contextlib
from unittest import mock

import numpy as np


@contextlib.contextmanager
def unchanged():
    from repro.core import distributed
    real = distributed.tron

    def tron(fgrad, hessd, beta0, cfg, **kw):
        return real(fgrad, hessd, beta0, cfg, **kw)._replace(beta=beta0)

    with mock.patch.object(distributed, "tron", tron):
        yield


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp

    from repro.api import plans
    real = plans._distributed

    def twice_first_half(a):
        h = a.shape[0] // 2
        return jnp.concatenate([a[:h], a[:h], a[2 * h:]])

    def _distributed(config, mesh, X, y, *args, **kw):
        return real(config, mesh, twice_first_half(X), twice_first_half(y),
                    *args, **kw)

    with mock.patch.object(plans, "_distributed", _distributed):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.core import distributed
    with mock.patch.object(distributed, "_psum_dp", lambda x, axes: x):
        yield


@contextlib.contextmanager
def altered_answer():
    from repro.api import infer
    real = infer.BucketedDecider.__call__

    def call(self, X):
        out = np.array(real(self, X))
        out[:1] += 1e-2 * np.max(np.abs(out)) + 1e-3
        return out

    with mock.patch.object(infer.BucketedDecider, "__call__", call):
        yield


@contextlib.contextmanager
def half_served():
    from repro.api import infer
    real = infer.BucketedDecider.__call__

    def call(self, X):
        out = np.array(real(self, X))
        out[(out.shape[0] + 1) // 2:] = 0.0
        return out

    with mock.patch.object(infer.BucketedDecider, "__call__", call):
        yield


ALL = {"unchanged": unchanged, "half_batch": half_batch,
       "no_exchange": no_exchange, "altered_answer": altered_answer,
       "half_served": half_served}

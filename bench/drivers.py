"""The two ways a cell drives the program, one per traffic kind.

``FitDriver`` (``fit_repeat``): set-up builds one ``KernelMachine`` on the
cell's mesh and fits it once, which compiles; the window calls ``fit`` on
the same machine and data back to back. ``ServeDriver`` (``open_loop``):
set-up registers a machine with seeded weights in a ``ModelRegistry``,
warms every bucket and starts a ``ServeEngine``; the window sends the mix's
schedule through ``ServeEngine.submit``.

Each driver then frees the program's state and compares what the window
produced with ``bench.reference`` (``check``). From the program the
benchmark takes only its public entry points and the counters it returns.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, List

import jax
import numpy as np

from bench import data, loadgen, reference

Span = Callable[[str], object]


def _mesh(cfg: dict, devices):
    from repro.core.compat import make_mesh
    return make_mesh(tuple(cfg["mesh"]), ("data",), devices=list(devices))


def _machine(cfg: dict, mesh):
    from repro.api import KernelMachine, MachineConfig
    from repro.core import KernelSpec, TronConfig
    return KernelMachine(MachineConfig(
        kernel=KernelSpec(cfg["kernel"], sigma=float(cfg["sigma"])),
        loss=cfg["loss"], lam=float(cfg["lam"]), solver="tron",
        plan=cfg["plan"], backend=cfg["backend"],
        dtype_policy=cfg["dtype_policy"], m=int(cfg["m"]),
        tron=TronConfig(**cfg["tron"])), mesh=mesh)


def _gap(got: float, want: float) -> float:
    return abs(got - want) / abs(want)


class FitDriver:
    def __init__(self, cell, seed: int, seconds: float, devices, span: Span):
        self.cfg, self.seed, self.span = cell.config, seed, span
        self.devices = list(devices)

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        cfg = self.cfg
        self.mesh = _mesh(cfg, self.devices)
        self.X, self.y = data.rows(
            cfg, self.seed, "train", cfg["n"],
            shardings=(NamedSharding(self.mesh, P("data", None)),
                       NamedSharding(self.mesh, P("data"))))
        self.basis = jax.device_put(data.basis(cfg, self.seed, self.X),
                                    NamedSharding(self.mesh, P()))
        self.km = _machine(cfg, self.mesh)
        self._fit()                        # compiles: part of set-up

    def _fit(self):
        self.km.fit(self.X, self.y, self.basis)
        jax.block_until_ready(self.km.state_["beta"])
        return self.km.result_

    def window(self, seconds: float, rec: dict) -> None:
        fits: List[Dict] = []
        start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with self.span("bench.fit"):
                r = self._fit()
            t1 = time.perf_counter()
            fits.append({"t0": t0, "t1": t1, "n_fg": r.n_fg,
                         "n_hd": r.n_hd, "n_iter": r.n_iter})
            if t1 - start >= seconds:
                break
        cfg = self.cfg
        rec["fits"] = fits
        rec["work"] = {"n": cfg["n"], "m": cfg["m"], "d": cfg["d"], "k": 1}
        rec["attempted"], rec["failed"] = len(fits), 0
        r = self.km.result_
        self.out = {"f_hist": np.asarray(r.tron.f_hist)[: r.n_iter + 1],
                    "gnorm": float(r.gnorm),
                    "beta": np.asarray(self.km.state_["beta"], np.float64)}

    def release(self) -> None:
        del self.km

    def reference(self, mode: str) -> reference.Fit:
        cfg, dev = self.cfg, self.devices[0]
        return reference.fit(
            jax.device_put(self.X, dev), jax.device_put(self.y, dev),
            jax.device_put(self.basis, dev), lam=cfg["lam"],
            sigma=cfg["sigma"], cfg=reference.Tron(**cfg["tron"]), mode=mode)

    @staticmethod
    def compare(got: dict, ref: reference.Fit) -> Dict[str, float]:
        """The numbers ``correct`` compares: the worst gap of the objective
        over TRON's iterations, and the gaps of the final gradient's norm
        and of the norm of beta's change from beta0 = 0."""
        fp, fr = got["f_hist"], ref.f_hist
        loss = max(_gap(a, b) for a, b in zip(fp, fr)) \
            if len(fp) == len(fr) else 1.0
        return {"loss_gap": loss,
                "grad_gap": _gap(got["gnorm"], ref.gnorm),
                "change_gap": _gap(np.linalg.norm(got["beta"]),
                                   np.linalg.norm(ref.beta))}

    def check(self) -> Dict[str, float]:
        return self.compare(self.out, self.reference(reference.HIGHEST))


class ServeDriver:
    def __init__(self, cell, seed: int, seconds: float, devices, span: Span):
        self.cfg, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.seconds, self.span = seconds, span
        self.devices = list(devices)

    def setup(self) -> None:
        from repro.serve import EngineConfig, ModelRegistry, ServeEngine
        cfg = self.cfg
        mesh = _mesh(cfg, self.devices)
        X, _ = data.rows(cfg, self.seed, "train", cfg["n"])
        self.basis = data.basis(cfg, self.seed, X)
        del X
        self.beta = data.weights(cfg, self.seed)
        self.pool = np.asarray(data.rows(cfg, self.seed, "test",
                                         cfg["n_test"])[0])
        self.plan(self.traffic, self.seconds)
        km = _machine(cfg, mesh)
        km.state_ = {"basis": self.basis, "beta": self.beta}
        self.registry = ModelRegistry(max_batch=cfg["engine"]["max_batch"])
        self.registry.add("m0", km)
        self.registry.warmup()
        self.engine = ServeEngine(self.registry,
                                  EngineConfig(**cfg["engine"]))
        for p in self.payloads[:16]:       # first dispatches, off the clock
            self.engine.submit(p).result()

    def plan(self, traffic: dict, seconds: float) -> None:
        """The window's schedule and the rows of each request (seeded)."""
        rng = data.host_rng(self.seed)
        self.sched = loadgen.schedule(traffic, seconds, rng)
        self.starts = np.concatenate([[0], np.cumsum(self.sched.rows)])
        self.rows = self.pool[rng.integers(0, self.pool.shape[0],
                                           self.starts[-1])]
        self.payloads = [self.rows[a:b] for a, b in zip(self.starts[:-1],
                                                        self.starts[1:])]

    def window(self, seconds: float, rec: dict) -> None:
        del seconds                        # the schedule fixes the window
        self.margins = np.zeros(len(self.rows), np.float32)
        starts = self.starts

        def on_done(i, answer):
            self.margins[starts[i]:starts[i + 1]] = answer

        before = self.engine.metrics.snapshot()
        loop = loadgen.OpenLoop(lambda i: self.engine.submit(self.payloads[i]),
                                self.sched.due_s, self.span, on_done)
        start = time.perf_counter() + 0.01
        loop.run(start)
        loop.join(close=time.perf_counter())
        after = self.engine.metrics.snapshot()
        answered = ~np.isnan(loop.done)
        end = np.max(loop.done[answered]) if answered.any() else start
        self.loop = loop
        rec["serve"] = {
            # a request that failed or never came counts as answered at the
            # end of the window: missing any limit a tail can be held to
            "latency_s": np.where(answered, loop.done, end) - loop.due,
            "late_s": loop.sent - loop.due,
            "dispatched_rows": after["dispatched_rows"]
            - before["dispatched_rows"],
            "padded_rows": after["padded_rows"] - before["padded_rows"],
        }
        rec["attempted"] = len(loop.due)
        rec["failed"] = int(len(loop.due) - answered.sum())

    def release(self) -> None:
        self.engine.stop()
        del self.engine, self.registry

    def served(self):
        """(rows, margins) of every answered request, and the count of the
        unanswered."""
        answered = ~np.isnan(self.loop.done)
        per_row = np.repeat(answered, self.sched.rows)
        return (self.rows[per_row], self.margins[per_row],
                int(len(answered) - answered.sum()))

    def reference(self, x, mode: str):
        dev = self.devices[0]
        return np.asarray(reference.margins(
            jax.device_put(x, dev), jax.device_put(self.basis, dev),
            jax.device_put(self.beta, dev), sigma=float(self.cfg["sigma"]),
            mode=mode))

    def check(self) -> Dict[str, float]:
        x, got, unanswered = self.served()
        return {"margin_gap": reference.rel_err(
                    got, self.reference(x, reference.HIGHEST)),
                "unanswered": float(unanswered)}


def make(cell, seed: int, seconds: float, devices, span: Span):
    kind = loadgen.kind(cell.traffic)
    cls = {"fit_repeat": FitDriver, "open_loop": ServeDriver}[kind]
    return cls(cell, seed, seconds, devices, span)

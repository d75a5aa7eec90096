"""The one result type every solver returns.

Subsumes the old ``TronResult`` (tron/linearized/rff paths) and
``StageResult`` (stage-wise growth: one FitResult per ``partial_fit`` call,
collected on ``KernelMachine.history_``). Counters that a solver does not
track (e.g. ppacksvm has no gradient norm) are NaN/0 rather than absent, so
downstream tables can treat results uniformly.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

from repro import obs
from repro.core.tron import TronResult


@dataclasses.dataclass(frozen=True)
class FitResult:
    solver: str
    plan: str
    m: int                    # parameter count (basis size / features / support)
    f: float                  # final objective (NaN when the solver has none)
    gnorm: float
    n_iter: int               # outer iterations / SGD communication rounds
    n_fg: int
    n_hd: int
    converged: bool
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @classmethod
    def from_tron(cls, res: TronResult, *, solver: str, plan: str, m: int,
                  extras: Optional[Dict[str, Any]] = None) -> "FitResult":
        """Column-batched (one-vs-rest) TronResults carry (K,) per-column
        f/gnorm/converged; the scalar summary here is the separable total
        objective (sum), the worst gradient norm, and all-columns
        convergence. The raw per-column result stays in ``extras['tron']``.
        Reading the result waits for the device: an ``estimator.wait`` span.
        """
        import numpy as np
        ex = {"tron": res}
        if extras:
            ex.update(extras)
        with obs.span("estimator.wait"):
            f = np.asarray(res.f)
            gnorm = np.asarray(res.gnorm)
            conv = np.asarray(res.converged)
        return cls(solver=solver, plan=plan, m=m,
                   f=float(f.sum()), gnorm=float(gnorm.max()),
                   n_iter=int(res.n_iter), n_fg=int(res.n_fg),
                   n_hd=int(res.n_hd), converged=bool(conv.all()),
                   extras=ex)

    @property
    def tron(self) -> Optional[TronResult]:
        return self.extras.get("tron")

    def __repr__(self):  # keep array-laden extras out of logs
        f = "nan" if math.isnan(self.f) else f"{self.f:.6g}"
        return (f"FitResult(solver={self.solver!r}, plan={self.plan!r}, "
                f"m={self.m}, f={f}, n_iter={self.n_iter}, "
                f"converged={self.converged})")

"""Algorithmic operations and bytes of the kernel machine's work, from the
cell's shapes alone (unpadded n, m, d and K), so that the same work reads
the same whatever implements it.

A kmvp pass over rows x (r, d) against the basis z (m, d) with k right-hand
sides computes the gaussian gram's cross term (2 r m d) and contracts it
(2 r m k): ``2 r m (d + k)`` operations. It must read x, z and the
right-hand side and write the result once: ``4 (r d + m d + m k + r k)``
bytes in float32.
"""
from __future__ import annotations

import dataclasses

F32 = 4


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, times: float) -> "Work":
        return Work(self.flops * times, self.bytes * times)

    __rmul__ = __mul__



def kmvp_pass(rows: int, m: int, d: int, k: int = 1) -> Work:
    """One fused kmvp call (``C(x, z) @ B`` or ``C(x, z)^T @ V``)."""
    return Work(2.0 * rows * m * (d + k),
                F32 * (rows * d + m * d + m * k + rows * k))


def fused_eval(n: int, m: int, d: int, k: int = 1) -> Work:
    """One f/g or Hd evaluation under the fused plan: a forward and a
    transposed pass over the rows, and a forward pass over the basis rows
    for the W term."""
    return 2 * kmvp_pass(n, m, d, k) + kmvp_pass(m, m, d, k)


def fit_kmvp(n: int, m: int, d: int, evals: int, k: int = 1) -> Work:
    """Every kmvp call of a fused-plan fit of ``evals`` evaluations."""
    return evals * fused_eval(n, m, d, k)


def fit_required(n: int, m: int, d: int, evals: int, k: int = 1) -> Work:
    """The work a fit requires whatever the plan: build C (n, m) and W
    (m, m) once, then per evaluation two contractions with C and one with W.
    Recomputing C, as the fused plan does, is not counted. Bytes: read X and
    the basis once, write and read C and W once per use."""
    build = Work(2.0 * n * m * d + 2.0 * m * m * d,
                 F32 * (n * d + m * d + n * m + m * m))
    per_eval = Work(2 * 2.0 * n * m * k + 2.0 * m * m * k,
                    F32 * (2 * n * m + m * m))
    return build + evals * per_eval


def least_time_s(work: Work, peak_flops: float, peak_bytes_s: float
                 ) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_ops, t_mem = work.flops / peak_flops, work.bytes / peak_bytes_s
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")

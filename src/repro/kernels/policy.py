"""Dtype policy for the kernel compute path (ROADMAP item 3).

One frozen, hashable object answers every "which dtype?" question the hot
path asks, so the answer is threaded as *data* from ``MachineConfig`` down
to the Pallas tiles instead of being hardcoded per call site:

    compute — dtype operands are cast to before the tile matmuls (what the
              MXU multiplies: bf16 doubles effective throughput vs fp32 on
              the same math; fp16 is the CPU-fallback analogue).
    accum   — ``preferred_element_type`` of every tile contraction and the
              dtype of the Pallas VMEM distance accumulator. fp32 always:
              low-precision *accumulation* is where kernel machines actually
              lose margins, and the MXU gives fp32 accumulation for free.
    param   — dtype of the optimizer state (beta, g, delta, Hd). Kept fp32
              so TRON's trust-region logic is numerically untouched by the
              compute policy.
    store   — dtype checkpointed arrays are written in (``int8`` means the
              symmetric per-column quantization in ``repro.checkpoint.quant``).

The default policy is all-fp32 and every policied code path is written so
that the fp32 policy computes bitwise what the pre-policy code computed on
the CPU (asserted by tests); on a TPU it also asks every dot for full f32
precision (``DtypePolicy.precision``).

Policies are named (``"fp32"``, ``"bf16"``, ``"fp16"``) so they JSON
round-trip through ``MachineConfig`` and checkpoints as plain strings.
Fields are dtype *names* (strings), keeping the dataclass hashable — it
rides through ``jax.jit`` static arguments unchanged.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """What the kernel layer computes, accumulates, optimizes, and stores in.

    All fields are numpy/jax dtype names. ``store`` additionally accepts
    ``"int8"``, which selects quantized checkpointing (see
    ``repro.checkpoint.quant``) rather than a plain array cast.
    """

    compute: str = "float32"
    accum: str = "float32"
    param: str = "float32"
    store: str = "float32"

    def __post_init__(self):
        for field in ("compute", "accum", "param"):
            jnp.dtype(getattr(self, field))       # fail fast on typos
        if self.store != "int8":
            jnp.dtype(self.store)

    # jnp dtypes on demand (the string fields keep the dataclass hashable)
    @property
    def compute_dtype(self):
        return jnp.dtype(self.compute)

    @property
    def accum_dtype(self):
        return jnp.dtype(self.accum)

    @property
    def param_dtype(self):
        return jnp.dtype(self.param)

    @property
    def is_default(self) -> bool:
        """True when every dtype is fp32 — the bitwise-unchanged fast path."""
        return (self.compute == self.accum == self.param == "float32"
                and self.store == "float32")

    @property
    def precision(self):
        """``precision`` of every tile contraction. On a TPU an f32 dot at
        the default precision runs as one bf16 MXU pass, and the gaussian
        distance ||x||^2 + ||z||^2 - 2 x.z amplifies that rounding by
        cancellation, so fp32 asks for HIGHEST (full f32). Low-precision
        policies multiply their own dtype, which DEFAULT does exactly."""
        return (jax.lax.Precision.HIGHEST if self.compute == "float32"
                else jax.lax.Precision.DEFAULT)

    def np_compute_dtype(self) -> np.dtype:
        """The compute dtype as a numpy dtype — what request payloads and
        host-side chunk transfers are cast to. bf16 resolves through
        ml_dtypes (shipped with jax), so plain numpy arrays can hold it."""
        return np.dtype(jnp.dtype(self.compute).name)


FP32 = DtypePolicy()
BF16 = DtypePolicy(compute="bfloat16")
FP16 = DtypePolicy(compute="float16")

#: Named policies — the values ``MachineConfig.dtype_policy`` accepts.
POLICIES = {"fp32": FP32, "bf16": BF16, "fp16": FP16}


def get_policy(policy) -> DtypePolicy:
    """Resolve a policy name / DtypePolicy / None (-> fp32 default)."""
    if policy is None:
        return FP32
    if isinstance(policy, DtypePolicy):
        return policy
    if isinstance(policy, str):
        try:
            return POLICIES[policy]
        except KeyError:
            raise ValueError(
                f"unknown dtype policy {policy!r}; registered: "
                f"{sorted(POLICIES)}") from None
    raise TypeError(f"dtype policy must be a name, DtypePolicy, or None; "
                    f"got {type(policy).__name__}")

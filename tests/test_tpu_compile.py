"""The Pallas kernels compile for a TPU v5e at the widths users run.

Interpret mode on the CPU cannot see what the chip's compiler refuses
(tiles not aligned to its layout, more VMEM than a kernel may hold). The
TPU compiler is installed here and compiles for a chip that is described,
not attached, so these tests lower ``ops.kmvp_fwd``/``kmvp_t``/``gram``
with ``interpret=False`` for one chip of a described ``v5e:2x2`` and
check that each became a Mosaic kernel (``tpu_custom_call``):

* covtype width: n=16384 rows, d=54, m=16384 basis points, k=1;
* mnist8m width: d=784, k=10 one-vs-rest columns;

each under the fp32 and bf16 policies. The kmvp calls must keep the names
the benchmark's trace reader looks for (``kmvp_fwd.<n>``/``kmvp_t.<n>``),
and only k > 1 may take a 128-lane RHS or output: k = 1 contracts on the
VPU. Nothing runs, so nothing about results or speed is checked here.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

WIDTHS = {"covtype": dict(n=16384, d=54, m=16384, k=1),
          "mnist8m": dict(n=16384, d=784, m=16384, k=10)}
TILES = dict(bn=512, bm=256, bd=256)    # the wrappers clamp bd to d's lanes


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compile
    cache off: a compile for a described chip is written to it but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("op", ["kmvp_fwd", "kmvp_t", "gram"])
def test_kernel_compiles_for_v5e(one_chip, op, width, policy):
    w = WIDTHS[width]
    x = _sds((w["n"], w["d"]), one_chip)
    z = _sds((w["m"], w["d"]), one_chip)
    args = {"kmvp_fwd": (x, z, _sds((w["m"], w["k"]), one_chip)),
            "kmvp_t": (x, z, _sds((w["n"], w["k"]), one_chip)),
            "gram": (x, z)}[op]
    compiled = getattr(ops, op).lower(*args, interpret=False, policy=policy,
                                      **TILES).compile()
    calls = _kernel_calls(compiled.as_text())
    assert calls
    if op == "gram":
        return
    (family, out, operands), = calls
    # kmvp_roofline finds the kernels in the trace by these two families.
    assert family == op
    lanes = {f"f32[{w['n']},128]", f"f32[{w['m']},128]"}
    rhs_and_out = {operands[2], out}
    if w["k"] == 1:     # the VPU contraction: no 128-lane RHS or output
        assert not rhs_and_out & lanes, rhs_and_out
    else:               # the MXU contraction over k padded to 128 lanes
        assert rhs_and_out <= lanes, rhs_and_out


def _kernel_calls(hlo: str):
    """(name family, result shape, operand shapes) of every Mosaic kernel
    call in a compiled module's text, e.g. ``%kmvp_fwd.1 = f32[16384,1]
    custom-call(...), ..., operand_layout_constraints={f32[..]{1,0}, ..}``."""
    calls = []
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        head = re.search(r"%(\w+)\.\d+ = (\w+\[[\d,]*\])", line)
        operands = re.search(
            r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", line)
        calls.append((head[1], head[2],
                      re.findall(r"\w+\[[\d,]*\]", operands[1])))
    return calls

"""Multi-controller training + serving, proven by a simulated fleet.

The paper's deployment claim (§4) is that Algorithm 1 distributes as an
AllReduce of O(m) vectors over partitioned data, tolerating worker loss.
These tests reproduce that claim on one machine: N subprocesses, each a
"host" with its own fake local devices, joined by ``jax.distributed``
into one global mesh (tests/multihost/rig.py).

Three properties are load-bearing:

* **Parity** — the fit over 2 and 4 processes matches the single-process
  beta as closely as two f32 solves can stop (the derived plateau
  tolerance of ``conftest.f32_plateau_rtol``), and 2-process x 2-device
  equals 4-process x 1-device *bitwise* (same 4-device global mesh, same
  reduction order): the distribution layer changes where rows live, not
  the math.
* **O(m) traffic** — the cross-host payload of one training chunk
  evaluation is counted from the traced jaxpr (not claimed): a handful
  of m-vectors, independent of chunk_rows; a served request moves
  O(batch) bytes, independent of m.
* **Fail fast** — SIGKILLing a worker mid-collective surfaces a clean,
  attributable error within the watchdog budget instead of a hang.
"""
import numpy as np
import pytest
from conftest import f32_plateau_rtol

from multihost.rig import FleetError, run_fleet

pytestmark = [pytest.mark.slow,
              pytest.mark.requires_devices(4),
              pytest.mark.requires_multiprocess(timeout=1500)]

PLANS = ("stream", "otf_shard")


def _rel_l2(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("plan", PLANS)
def test_multihost_parity_and_elasticity(plan):
    """2- and 4-process fits match 1-process within the f32 plateau
    tolerance (lam 0.1, sigma 1 as in worker._config; a few 1e-3 of
    ||beta|| here); 2x2 == 4x1 bitwise. All three fleets share the
    4-device global mesh."""
    ref = run_fleet("fit", 1, 4, extra=[plan]).result
    two = run_fleet("fit", 2, 2, extra=[plan]).result
    four = run_fleet("fit", 4, 1, extra=[plan]).result

    assert ref["n_devices"] == two["n_devices"] == four["n_devices"] == 4
    assert two["num_processes"] == 2 and four["num_processes"] == 4
    rel2 = _rel_l2(two["beta"], ref["beta"])
    rel4 = _rel_l2(four["beta"], ref["beta"])
    rtol = float(f32_plateau_rtol(ref["f"], 0.1, ref["basis"], 1.0,
                                  ref["beta"]))
    assert rel2 < rtol, f"2-process beta diverged: rel l2 {rel2:.2e} " \
        f"(tolerance {rtol:.2e})"
    assert rel4 < rtol, f"4-process beta diverged: rel l2 {rel4:.2e} " \
        f"(tolerance {rtol:.2e})"
    # process count is a deployment knob, not a numerical one: identical
    # global device count -> identical reduction order -> identical bits
    assert two["beta_sha"] == four["beta_sha"], \
        "2proc x 2dev and 4proc x 1dev disagree bitwise on the same mesh"


def test_multihost_collective_payload_is_o_m():
    """Counted from the traced jaxpr on a real 2-process spanning mesh:
    training moves O(m) bytes per chunk evaluation (f/g psums), serving
    moves O(batch) bytes per request — never O(n), never O(chunk_rows)."""
    out = run_fleet("payload", 2, 2).result
    m, itemsize = out["m"], out["itemsize"]
    # f/g: one scalar + one (m,) psum; Hd: one (m,) psum. c=4 leaves room
    # for an implementation to psum one extra m-vector, not a data-sized one.
    assert 0 < out["fg_chunk_bytes"] <= 4 * m * itemsize, out
    assert 0 < out["hd_chunk_bytes"] <= 4 * m * itemsize, out
    assert out["fg_chunk_bytes"] < out["chunk_rows"] * itemsize, \
        "per-chunk traffic scales with the data partition, not with m"
    assert 0 < out["serve_request_bytes"] <= 4 * out["max_batch"] * itemsize, \
        out


def test_multihost_worker_death_fails_fast():
    """SIGKILL one worker mid-lockstep: the fleet must fail attributably
    within the watchdog budget — never hang until the test timeout."""
    with pytest.raises(FleetError) as ei:
        run_fleet("spin", 2, 1, kill=(1, 8.0), timeout=120)
    err = ei.value
    assert err.returncodes[1] == -9, err.returncodes
    assert "process 1" in str(err)
    assert err.elapsed < 90, \
        f"death took {err.elapsed:.1f}s to surface (watchdog asleep?)"

"""Simulated multi-controller fleet runner for the multihost tests.

Spawns N copies of ``tests/multihost/worker.py`` — one subprocess per
simulated host, each forcing its own local device count via ``XLA_FLAGS``
*before* jax imports and joining a ``jax.distributed`` cluster on a
freshly bound localhost port. The rig is the fault model of the paper's
Hadoop deployment in miniature:

* a watchdog polls the fleet and kills every survivor the moment one
  worker exits nonzero (a hung gloo collective can never outlive the
  test timeout);
* ``kill=(pid, after_s)`` SIGKILLs a chosen worker mid-run to prove
  worker loss surfaces as a fast, attributable :class:`FleetError`
  rather than a hang;
* ``faults=FaultPlan`` generalizes that arm: the plan's fleet schedule
  (``.kill(pid, after_s)`` / ``.stall(pid, after_s, duration_s)``) is
  executed by the watchdog — SIGSTOP/SIGCONT stalls model a straggler or
  a paused VM rather than a death — and the plan's in-process rules ride
  into every worker via the ``REPRO_FAULTS`` environment variable;
* per-process logs are captured and attached to every failure.

Process 0's final stdout line is the worker's JSON result payload.
"""
from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
WORKER = os.path.join(_HERE, "worker.py")
SRC = os.path.join(_HERE, "..", "..", "src")


def free_port() -> int:
    """A currently free localhost TCP port for the coordinator."""
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


@dataclass
class FleetResult:
    """A successful fleet run: process 0's JSON + per-process logs."""
    result: dict
    logs: List[str]
    returncodes: List[int]
    elapsed: float


class FleetError(RuntimeError):
    """A worker died (or the fleet hung): carries exit codes + log tails."""

    def __init__(self, message: str, returncodes: Sequence[Optional[int]],
                 logs: Sequence[str], elapsed: float):
        self.returncodes = list(returncodes)
        self.logs = list(logs)
        self.elapsed = elapsed
        tails = "\n".join(
            f"--- process {i} (rc={rc}) ---\n" + "\n".join(
                log.strip().splitlines()[-8:])
            for i, (rc, log) in enumerate(zip(returncodes, logs)))
        super().__init__(f"{message}\n{tails}")


def run_fleet(task: str, num_processes: int, devices_per_proc: int = 1, *,
              extra: Sequence[str] = (), timeout: float = 600.0,
              kill: Optional[Tuple[int, float]] = None,
              faults: Optional[Any] = None,
              env_extra: Optional[Dict[str, str]] = None) -> FleetResult:
    """Run ``worker.py <task> <nproc> <pid> <port> [extra...]`` N times.

    ``kill=(pid, after_s)`` SIGKILLs worker ``pid`` once it has been
    alive ``after_s`` seconds (the fault-injection arm); ``faults`` (a
    :class:`repro.faults.FaultPlan`) carries a whole schedule of kill and
    SIGSTOP/SIGCONT stall events, plus in-process rules shipped to every
    worker via ``REPRO_FAULTS``. Raises :class:`FleetError` on any
    nonzero exit or on timeout; the watchdog guarantees the failure is
    reported within ~``timeout`` seconds even when survivors block inside
    a collective.
    """
    events: List[Dict[str, Any]] = []
    if kill is not None:
        events.append({"kind": "kill", "pid": int(kill[0]),
                       "at": float(kill[1])})
    if faults is not None:
        events.extend(dict(e) for e in faults.schedule)
    port = free_port()
    workdir = tempfile.mkdtemp(prefix="mh-fleet-")
    procs: List[subprocess.Popen] = []
    logpaths = [os.path.join(workdir, f"proc{p}.log")
                for p in range(num_processes)]
    try:
        for p in range(num_processes):
            env = dict(os.environ)
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={devices_per_proc}")
            env.setdefault("JAX_PLATFORMS", "cpu")
            env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
            if faults is not None and faults.rules:
                env["REPRO_FAULTS"] = faults.to_json()
            env.update(env_extra or {})
            procs.append(subprocess.Popen(
                [sys.executable, WORKER, task, str(num_processes), str(p),
                 str(port), *extra],
                stdout=open(logpaths[p], "wb"), stderr=subprocess.STDOUT,
                env=env, cwd=workdir))

        t0 = time.monotonic()
        while True:
            rcs = [pr.poll() for pr in procs]
            elapsed = time.monotonic() - t0
            _run_events(events, elapsed, procs, rcs)
            if all(rc is not None for rc in rcs):
                break
            if any(rc not in (None, 0) for rc in rcs) \
                    or elapsed > timeout:
                for pr in procs:
                    if pr.poll() is None:
                        pr.kill()
                for pr in procs:
                    pr.wait()
                rcs = [pr.poll() for pr in procs]
                if elapsed > timeout:
                    raise FleetError(
                        f"fleet timed out after {elapsed:.1f}s "
                        f"(task={task!r}, {num_processes} processes)",
                        rcs, _read_logs(logpaths), elapsed)
                break
            time.sleep(0.05)

        rcs = [pr.returncode for pr in procs]
        logs = _read_logs(logpaths)
        elapsed = time.monotonic() - t0
        if any(rc != 0 for rc in rcs):
            dead = next(i for i, rc in enumerate(rcs) if rc != 0)
            raise FleetError(
                f"process {dead} of task {task!r} exited rc={rcs[dead]}; "
                f"remaining workers were killed {elapsed:.1f}s in",
                rcs, logs, elapsed)
        # The worker's result is its last JSON line; the collectives
        # runtime may still write status lines to the shared log after it
        # (stdout is block-buffered, the runtime's stderr is not).
        try:
            result = json.loads(next(
                ln for ln in reversed(logs[0].strip().splitlines())
                if ln.startswith("{")))
        except (StopIteration, ValueError) as e:
            raise FleetError(
                f"process 0 of task {task!r} produced no JSON result ({e})",
                rcs, logs, elapsed)
        return FleetResult(result=result, logs=logs, returncodes=rcs,
                           elapsed=elapsed)
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
        shutil.rmtree(workdir, ignore_errors=True)


def _run_events(events: List[Dict[str, Any]], elapsed: float,
                procs: Sequence[subprocess.Popen],
                rcs: Sequence[Optional[int]]) -> None:
    """Execute due fleet fault events (kill / stall) against live workers.

    SIGKILL needs no unstick step (it terminates stopped processes too);
    stalls send SIGSTOP at ``at`` and SIGCONT at ``at + duration`` —
    peers block inside their next collective until the straggler resumes,
    so stall durations must stay well under the collective timeout."""
    for e in events:
        pid = e["pid"]
        if not 0 <= pid < len(procs) or rcs[pid] is not None:
            continue
        if e["kind"] == "kill":
            if not e.get("done") and elapsed >= e["at"]:
                procs[pid].kill()
                e["done"] = True
        elif e["kind"] == "stall":
            if not e.get("stopped") and elapsed >= e["at"]:
                procs[pid].send_signal(signal.SIGSTOP)
                e["stopped"] = True
            if e.get("stopped") and not e.get("done") \
                    and elapsed >= e["at"] + e["duration"]:
                try:
                    procs[pid].send_signal(signal.SIGCONT)
                except ProcessLookupError:
                    pass
                e["done"] = True


def _read_logs(paths: Sequence[str]) -> List[str]:
    out = []
    for path in paths:
        try:
            with open(path, "r", errors="replace") as fh:
                out.append(fh.read())
        except OSError:
            out.append("")
    return out

"""Automatic fleet recovery: supervise training workers, restart from the
latest committed checkpoint.

The paper runs its AllReduce tree on Hadoop precisely to inherit
Map-Reduce's fault tolerance (§4) — a lost worker's task is re-run, the
job survives. The repo's simulated fleet (PR 8) proves worker death is
*detected* (fail-fast watchdog) and PR 7 proves a human can ``--resume``
bitwise; this module closes the loop so nobody has to be awake: the
:class:`Supervisor` spawns the training processes, watches them with the
same poll-loop idiom as the test rig, and on any worker death tears the
fleet down, waits a capped exponential backoff (with the deterministic
jitter of :class:`repro.util.retry.RetryPolicy`), and relaunches — with
``--resume`` as soon as the checkpoint directory holds a committed step.

Because PR 7's restore is *elastic*, recovery composes with degradation:
after ``shrink_after`` consecutive failures at the current process count
the supervisor shrinks the fleet P → P−1 (down to ``min_processes``) and
keeps going — forward progress on fewer hosts instead of a crash loop on
a persistently bad one. Single-topology restarts stay on PR 7's
canonical-trajectory guarantee: the recovered β is bitwise identical to
an uninterrupted run (tests/test_supervisor.py asserts this end to end).

Deliberately jax-free: the supervisor is a process manager. Children do
the jax work; the parent only needs subprocess, sockets and the stdlib.

All of a fleet's processes run on this host, so more than one process is
a CPU simulation only (each child fakes its devices). On a TPU host a
chip belongs to one process at a time: supervise one process there, which
drives every chip of the host.
"""
from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

from repro.util.retry import RetryPolicy

#: build_cmd(process_id, num_processes, port, resume) -> argv for one worker.
#: ``port`` is None for single-process fleets; ``resume`` is True once the
#: checkpoint directory holds a committed step.
BuildCmd = Callable[[int, int, Optional[int], bool], List[str]]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Restart policy knobs.

    ``max_restarts`` bounds relaunches across the whole run (0 = fail on
    the first death, i.e. PR 8's fail-fast behavior). Backoff before each
    relaunch is ``min(max_backoff_s, backoff_s * backoff_mult**(k-1))``
    for the k-th restart, plus deterministic jitter. ``shrink_after``
    consecutive failures at one process count shrink the fleet by one
    process (elastic degraded mode) down to ``min_processes``;
    ``attempt_timeout_s`` bounds any single attempt's wall time (a hung
    fleet counts as a failure)."""
    max_restarts: int = 3
    backoff_s: float = 0.5
    backoff_mult: float = 2.0
    max_backoff_s: float = 15.0
    jitter: float = 0.1
    poll_s: float = 0.05
    attempt_timeout_s: float = 900.0
    shrink_after: int = 2
    min_processes: int = 1


@dataclasses.dataclass
class SupervisorResult:
    """Outcome + per-attempt records (the fault-recovery benchmark's raw
    material: MTTR = ``death_detect_s``→next spawn = teardown + backoff)."""
    ok: bool
    restarts: int
    final_processes: int
    shrunk: bool
    total_s: float
    attempts: List[Dict[str, Any]]

    @property
    def final_attempt(self) -> Dict[str, Any]:
        return self.attempts[-1]


class SupervisorError(RuntimeError):
    """Raised when the restart budget is exhausted; carries log tails."""

    def __init__(self, message: str, attempts: List[Dict[str, Any]]):
        super().__init__(message)
        self.attempts = attempts


class Supervisor:
    """Spawn, watch, and restart a fleet of training processes.

    ``build_cmd`` maps (process_id, num_processes, port, resume) to one
    worker's argv — ``repro.launch.kernel_train`` builds its own child
    command line here, tests substitute ``python -c`` stubs. ``ckpt_dir``
    is polled (by file name only — no heavy imports) to decide when a
    relaunch can ``--resume``; None means every restart is from scratch.
    ``env`` is the base environment for every worker (default: inherit).
    """

    def __init__(self, build_cmd: BuildCmd, *, num_processes: int = 1,
                 ckpt_dir: Optional[str] = None,
                 config: SupervisorConfig = SupervisorConfig(),
                 env: Optional[dict] = None,
                 log_dir: Optional[str] = None,
                 say: Callable[[str], None] = print,
                 sleep: Callable[[float], None] = time.sleep):
        if num_processes < 1:
            raise ValueError(f"num_processes must be >= 1, got "
                             f"{num_processes}")
        self.build_cmd = build_cmd
        self.num_processes = int(num_processes)
        self.ckpt_dir = ckpt_dir
        self.cfg = config
        self.env = dict(os.environ if env is None else env)
        self.log_dir = log_dir or tempfile.mkdtemp(prefix="supervise-")
        self.say = say
        self.sleep = sleep
        # the backoff schedule reuses RetryPolicy's capped-exponential +
        # deterministic-jitter math; attempts map 1:1 onto retry attempts
        self._backoff = RetryPolicy(
            max_attempts=max(2, config.max_restarts + 1),
            backoff_s=config.backoff_s, backoff_mult=config.backoff_mult,
            max_backoff_s=config.max_backoff_s, jitter=config.jitter)

    # ----------------------------------------------------------- internals
    def latest_step(self) -> Optional[int]:
        """Newest committed step number in ``ckpt_dir`` (by file name —
        the commit protocol guarantees named step files are complete)."""
        if not self.ckpt_dir:
            return None
        import re
        try:
            names = os.listdir(self.ckpt_dir)
        except FileNotFoundError:
            return None
        steps = [int(mm.group(1)) for name in names
                 if (mm := re.match(r"^step-(\d{8})\.npz$", name))]
        return max(steps) if steps else None

    def _log_path(self, attempt: int, pid: int) -> str:
        return os.path.join(self.log_dir, f"attempt{attempt}.proc{pid}.log")

    def _tail(self, path: str, lines: int = 8) -> str:
        try:
            with open(path, "r", errors="replace") as fh:
                return "\n".join(fh.read().splitlines()[-lines:])
        except OSError:
            return "<no log>"

    def _run_attempt(self, attempt: int, nproc: int,
                     resume: bool) -> Dict[str, Any]:
        port = free_port() if nproc > 1 else None
        # captured BEFORE spawning: by the end of the attempt latest_step()
        # reflects the attempt's own commits, not where it started
        resumed_from = self.latest_step() if resume else None
        cmd0 = None
        procs, logs = [], []
        t0 = time.monotonic()
        for pid in range(nproc):
            cmd = self.build_cmd(pid, nproc, port, resume)
            if pid == 0:
                cmd0 = cmd
            log_path = self._log_path(attempt, pid)
            logs.append(log_path)
            fh = open(log_path, "w")
            procs.append(subprocess.Popen(
                cmd, stdout=fh, stderr=subprocess.STDOUT, env=self.env))
            fh.close()               # Popen duped the fd
        self.say(f"[supervise] attempt {attempt}: launched {nproc} "
                 f"process(es)" + (f", resuming from step "
                                   f"{resumed_from}" if resume else
                                   ", fresh start")
                 + (f" ({' '.join(cmd0[:3])} ...)" if cmd0 else ""))
        rcs: List[Optional[int]] = [None] * nproc
        death_detect_s = None
        timed_out = False
        while any(rc is None for rc in rcs):
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    rcs[i] = p.poll()
            if any(rc not in (None, 0) for rc in rcs):
                if death_detect_s is None:
                    death_detect_s = time.monotonic() - t0
                break
            if time.monotonic() - t0 > self.cfg.attempt_timeout_s:
                timed_out = True
                death_detect_s = time.monotonic() - t0
                break
            time.sleep(self.cfg.poll_s)
        # tear down survivors (no-op when everything exited cleanly)
        for i, p in enumerate(procs):
            if p.poll() is None:
                p.kill()
        for i, p in enumerate(procs):
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pass
            rcs[i] = p.returncode
        teardown_s = (time.monotonic() - t0 - death_detect_s) \
            if death_detect_s is not None else 0.0
        return {
            "attempt": attempt,
            "num_processes": nproc,
            "resumed_from": resumed_from,
            "returncodes": rcs,
            "ok": not timed_out and all(rc == 0 for rc in rcs),
            "timed_out": timed_out,
            "elapsed_s": time.monotonic() - t0,
            "death_detect_s": death_detect_s,
            "teardown_s": teardown_s,
            "backoff_s": 0.0,        # filled in by run() before relaunch
            "logs": logs,
        }

    # ---------------------------------------------------------------- API
    def run(self) -> SupervisorResult:
        t0 = time.monotonic()
        attempts: List[Dict[str, Any]] = []
        restarts = 0
        nproc = self.num_processes
        consecutive = 0               # failures at the current nproc
        shrunk = False
        while True:
            resume = self.latest_step() is not None
            rec = self._run_attempt(len(attempts) + 1, nproc, resume)
            attempts.append(rec)
            if rec["ok"]:
                self.say(f"[supervise] attempt {rec['attempt']} succeeded "
                         f"after {restarts} restart(s)")
                return SupervisorResult(
                    ok=True, restarts=restarts, final_processes=nproc,
                    shrunk=shrunk, total_s=time.monotonic() - t0,
                    attempts=attempts)
            dead = [i for i, rc in enumerate(rec["returncodes"]) if rc != 0]
            why = "timed out" if rec["timed_out"] else (
                f"worker(s) {dead} died "
                f"(returncodes={rec['returncodes']})")
            if restarts >= self.cfg.max_restarts:
                tails = "\n".join(
                    f"--- proc {i} (rc={rec['returncodes'][i]}) ---\n"
                    f"{self._tail(rec['logs'][i])}"
                    for i in range(len(rec["logs"])))
                raise SupervisorError(
                    f"[supervise] giving up: {why} and the restart budget "
                    f"({self.cfg.max_restarts}) is exhausted\n{tails}",
                    attempts)
            restarts += 1
            consecutive += 1
            if consecutive >= self.cfg.shrink_after and \
                    nproc > self.cfg.min_processes:
                nproc -= 1
                consecutive = 0
                shrunk = True
                self.say(f"[supervise] {self.cfg.shrink_after} consecutive "
                         f"failures — shrinking fleet to {nproc} "
                         f"process(es) (elastic degraded mode)")
            delay = self._backoff.delay(min(restarts,
                                            self._backoff.max_attempts - 1),
                                        label=f"supervise-{restarts}")
            rec["backoff_s"] = delay
            step = self.latest_step()
            self.say(f"[supervise] {why}; restarting "
                     + (f"from step {step}" if step is not None
                        else "from scratch")
                     + f" in {delay:.2f}s (restart {restarts}/"
                     f"{self.cfg.max_restarts})")
            self.sleep(delay)

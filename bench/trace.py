"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
From it this module takes:

- device operations: the events of each device plane's ``XLA Ops`` line
  (``/device:TPU:<i>``), each with its start, duration and name. The
  trace names an operation by its whole HLO instruction
  (``%kmvp_fwd.38 = f32[...] custom-call(...)``); the reduction keeps its
  family, the instruction's name without ``%`` and numeric suffix
  (``kmvp_fwd``), and counts each operation's self time, its duration less
  that of the operations it encloses (a ``while`` encloses its body's);
- host spans: the events whose name starts with ``bench.`` on the host
  plane, which the harness writes with ``jax.profiler.TraceAnnotation``;
- the window: the ``bench.window`` span.

and reduces them, per device and within the window, to the busy time (the
union of the operations' intervals), the time of each operation name, and
the idle gaps, each named by the innermost harness span that covers it.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

OPS_LINE = "XLA Ops"
DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
_FAMILY = re.compile(r"^%?([^\s=]+?)(\.\d+)*(\s|$)")
SPAN_PREFIX = "bench."
WINDOW = "bench.window"


class Event(NamedTuple):
    start: float      # seconds on the trace's clock
    end: float
    name: str


class Trace(NamedTuple):
    devices: Dict[str, List[Event]]     # plane name -> its operations
    spans: List[Event]                  # harness spans on the host


def newest(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def family(name: str) -> str:
    """``%kmvp_fwd.38 = f32[..] custom-call(..)`` -> ``kmvp_fwd``."""
    m = _FAMILY.match(name)
    return m.group(1) if m else name


def read(data: bytes) -> Trace:
    """A trace from the bytes of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(data)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        if DEVICE.match(plane.name):
            ops = [Event(e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                         * 1e-9, family(e.name))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            spans.extend(Event(e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9, e.name)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return Trace(devices=devices, spans=sorted(spans))


def window_of(trace: Trace) -> Tuple[float, float]:
    ws = [s for s in trace.spans if s.name == WINDOW]
    if len(ws) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(ws)}")
    return ws[0].start, ws[0].end


def _clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [Event(max(e.start, lo), min(e.end, hi), e.name)
            for e in events if e.end > lo and e.start < hi]


def busy_intervals(ops: List[Event]) -> List[Tuple[float, float]]:
    """The union of the operations' intervals, as sorted disjoint pairs."""
    out: List[Tuple[float, float]] = []
    for e in sorted(ops):
        if out and e.start <= out[-1][1]:
            if e.end > out[-1][1]:
                out[-1] = (out[-1][0], e.end)
        else:
            out.append((e.start, e.end))
    return out


def self_times(ops: List[Event]) -> List[Tuple[Event, float]]:
    """Each operation with its duration less that of the operations it
    encloses on the same line."""
    ops = sorted(ops, key=lambda e: (e.start, -e.end))
    own = [e.end - e.start for e in ops]
    stack: List[int] = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        if stack and e.end <= ops[stack[-1]].end:
            own[stack[-1]] -= e.end - e.start
        stack.append(i)
    return list(zip(ops, own))


def _label(spans: List[Event], t: float) -> str:
    """The innermost (shortest) harness span covering time t."""
    best: Optional[Event] = None
    for s in spans:
        if s.start <= t < s.end and s.name != WINDOW and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best is not None else "none"


def reduce(trace: Trace, top: int = 10) -> dict:
    """Per-device busy time and self time by operation family within the
    window, their means over the devices, and the longest idle gaps of
    device 0.

    Returns ``{"window_s", "busy_s", "devices": {plane: {"busy_s",
    "ops": {name: seconds}}}, "device_ops": [[name, seconds]],
    "idle_gaps": [[span, seconds]]}`` where ``busy_s`` is the mean over
    devices and ``device_ops`` the operations that took most time, summed
    over devices and divided by their number."""
    lo, hi = window_of(trace)
    if not trace.devices:
        raise ValueError("the trace holds no device operation")
    per_dev = {}
    total = collections.Counter()
    gaps: List[Tuple[float, str]] = []
    for k, (plane, ops) in enumerate(sorted(trace.devices.items())):
        ops = _clip(ops, lo, hi)
        busy = busy_intervals(ops)
        by_name = collections.Counter()
        for e, own in self_times(ops):
            by_name[e.name] += own
        total.update(by_name)
        per_dev[plane] = {"busy_s": sum(b - a for a, b in busy),
                          "ops": dict(by_name)}
        if k == 0:
            edges = [lo] + [t for ab in busy for t in ab] + [hi]
            longest = sorted(((b - a, a, b) for a, b in
                              zip(edges[::2], edges[1::2]) if b > a),
                             reverse=True)[:top]
            gaps = [(t, _label(trace.spans, (a + b) / 2))
                    for t, a, b in longest]
    n = len(per_dev)
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in per_dev.values()) / n,
        "devices": per_dev,
        "device_ops": [[name, t / n] for name, t in total.most_common(top)],
        "idle_gaps": [[label, t] for t, label in gaps],
    }


def op_time(reduction: dict, match) -> Dict[str, float]:
    """Self seconds of the operation families that satisfy ``match``, per
    device plane."""
    return {plane: sum(t for name, t in d["ops"].items() if match(name))
            for plane, d in reduction["devices"].items()}

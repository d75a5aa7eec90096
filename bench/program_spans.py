"""The program's own spans (``repro.obs``) inside a run's window, for the
per-layer metrics whose source is ``program_span``.

A program without the recorder gives nothing: the readers then return
None, and the run's line leaves the metric out."""
from __future__ import annotations


def durations_s(rec: dict, name: str):
    """Seconds of each ``name`` span that lies wholly inside the window,
    as an array; None where there is none, or where the recorder's ring
    overwrote records that may have lain inside the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    w = rec.get("window")
    if w is None:
        return None
    kept = obs.spans(name)
    # the ring keeps the newest records, and each of these names is
    # written by one thread in the order its spans end: records it has
    # overwritten ended no later than the oldest kept one, so they may lie
    # in the window unless that one ended before the window began
    if obs.dropped(name) and (not len(kept) or kept["end"][0] > w["start"]):
        return None
    s = kept[(kept["start"] >= w["start"]) & (kept["end"] <= w["end"])]
    return s["end"] - s["start"] if len(s) else None

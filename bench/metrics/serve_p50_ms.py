"""Median latency of every request of the window, from the moment it was
due to be sent to the moment its answer arrived (host clock)."""
from bench.stats import percentile_ms


def read(rec):
    serve = rec.get("serve")
    return None if serve is None else percentile_ms(serve["latency_s"], 50)

"""Device milliseconds of all-reduce operations per fit, on the device
where they take longest (profiler trace)."""
from bench.trace import op_time


def read(rec):
    fits, red = rec.get("fits"), rec.get("trace")
    if not fits or red is None or rec["chips"] < 2:
        return None
    per_dev = op_time(red, lambda name: "all-reduce" in name)
    worst = max(per_dev.values())
    return 1e3 * worst / len(fits) if worst > 0 else None

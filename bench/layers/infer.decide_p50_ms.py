"""Median time of one call of the bucketed decide arm: pad to the bucket,
run the executable, copy the margins to the host (the program's
``infer.decide`` spans)."""
from bench.program_spans import durations_s
from bench.stats import percentile_ms


def read(rec):
    d = durations_s(rec, "infer.decide")
    return None if d is None else percentile_ms(d, 50)

"""Median host time of one engine dispatch, from block assembly to the
last future set: the decide call and the Python around it (the program's
``serve.dispatch`` spans)."""
from bench.program_spans import durations_s
from bench.stats import percentile_ms


def read(rec):
    d = durations_s(rec, "serve.dispatch")
    return None if d is None else percentile_ms(d, 50)

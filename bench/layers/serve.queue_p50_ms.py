"""Median wait of a request in the serve engine's admission queue, from
``submit`` to the batcher's pop (the program's ``serve.queue`` spans)."""
from bench.program_spans import durations_s
from bench.stats import percentile_ms


def read(rec):
    d = durations_s(rec, "serve.queue")
    return None if d is None else percentile_ms(d, 50)

"""Host milliseconds per fit from the estimator's entry to its TRON
program enqueued: closures, tracing, lowering, compile or cache load and
the dispatch of what precedes TRON (the program's ``estimator.solve``
spans, summed over the window's fits)."""
from bench.program_spans import durations_s


def read(rec):
    fits = rec.get("fits")
    d = durations_s(rec, "estimator.solve")
    if not fits or d is None:
        return None
    return 1e3 * float(d.sum()) / len(fits)

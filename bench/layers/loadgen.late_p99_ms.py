"""99th percentile of how late the generator sent each request after it
was due (host clock): a starved generator must not read as a fast server."""
from bench.stats import percentile_ms


def read(rec):
    serve = rec.get("serve")
    return None if serve is None else percentile_ms(serve["late_s"], 99)

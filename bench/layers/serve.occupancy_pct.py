"""Rows asked for over rows dispatched, padding included, over the window:
the engine's ``ServeMetrics`` counters ``dispatched_rows / padded_rows``."""


def read(rec):
    serve = rec.get("serve")
    if serve is None or not serve["padded_rows"]:
        return None
    return 100.0 * serve["dispatched_rows"] / serve["padded_rows"]

"""Share of the serving window in which no operation ran on the device,
mean over the cell's chips (profiler trace)."""


def read(rec):
    red = rec.get("trace")
    if rec.get("serve") is None or red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])

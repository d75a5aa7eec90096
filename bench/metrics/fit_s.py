"""Wall seconds per ``KernelMachine.fit``: the whole time of the fits the
window ran back to back, over their count (host clock)."""


def read(rec):
    fits = rec.get("fits")
    if not fits:
        return None
    return (fits[-1]["t1"] - fits[0]["t0"]) / len(fits)

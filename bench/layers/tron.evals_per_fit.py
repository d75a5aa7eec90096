"""f/g and Hd evaluations per fit, from the program's ``TronResult``
counters (``n_fg + n_hd``)."""


def read(rec):
    fits = rec.get("fits")
    if not fits:
        return None
    return sum(f["n_fg"] + f["n_hd"] for f in fits) / len(fits)

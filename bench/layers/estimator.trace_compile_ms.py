"""Host milliseconds per fit spent in JAX's tracing, lowering and compile
events, persistent-cache loads included (``jax.monitoring``): what the
estimator pays on every fit for building its programs anew."""


def read(rec):
    fits = rec.get("fits")
    if not fits:
        return None
    log = rec["compile_log"]
    total = sum(log.seconds_between(f["t0"], f["t1"]) for f in fits)
    return 1e3 * total / len(fits)

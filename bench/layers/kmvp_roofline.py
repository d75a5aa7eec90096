"""Share of the roofline reached by the fused kmvp kernels: the least time
the chip could take for the algorithmic operations and bytes of every kmvp
call in the traced window (``bench.counts``, unpadded shapes), over the
summed device time of the kernels' events (profiler trace).

Every call is a forward or a transposed pass over the rows, or the forward
pass over the basis rows for the W term, all through the same two kernels;
the trace names their events ``%kmvp_fwd.<n> = ...`` and
``%kmvp_t.<n> = ...``, of the families below."""
from bench import counts
from bench.trace import op_time

KERNELS = ("kmvp_fwd", "kmvp_t")


def read(rec):
    fits, red, peak = rec.get("fits"), rec.get("trace"), rec.get("peak")
    if not fits or red is None or peak is None:
        return None
    t = max(op_time(red, lambda name: name in KERNELS).values())
    if t <= 0:
        return None
    w = rec["work"]
    evals = sum(f["n_fg"] + f["n_hd"] for f in fits)
    work = counts.fit_kmvp(w["n"], w["m"], w["d"], evals, w["k"]) \
        * (1.0 / rec["chips"])
    least, _ = counts.least_time_s(work, peak.bf16_flops, peak.hbm_bytes_s)
    return 100.0 * least / t

"""The whole fit's share of the chips' peak: the operations a fit requires
whatever the plan (build C and W once, two C contractions and one W
contraction per evaluation; recomputation not counted, ``bench.counts``)
over fit time x chips x the published bf16 peak."""
from bench import counts


def read(rec):
    fits, peak = rec.get("fits"), rec.get("peak")
    if not fits or peak is None:
        return None
    w = rec["work"]
    flops = sum(counts.fit_required(w["n"], w["m"], w["d"],
                                    f["n_fg"] + f["n_hd"], w["k"]).flops
                for f in fits)
    seconds = fits[-1]["t1"] - fits[0]["t0"]
    return 100.0 * flops / (seconds * rec["chips"] * peak.bf16_flops)

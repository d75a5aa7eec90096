"""bench/counts.py and bench/peaks.py against figures worked by hand."""
import bench_tiny  # noqa: F401  (puts the repository on sys.path)
import pytest

from bench import counts, peaks


def test_kmvp_pass_by_hand():
    # 2 r m (d + k) = 2 * 10 * 20 * (3 + 1) = 1600 operations;
    # 4 * (r d + m d + m k + r k) = 4 * (30 + 60 + 20 + 10) = 480 bytes
    w = counts.kmvp_pass(10, 20, 3, 1)
    assert w.flops == 1600.0
    assert w.bytes == 480.0


def test_fused_eval_and_fit_by_hand():
    # two row passes (1600 each) and one basis pass: 2*20*20*4 = 3200 ops,
    # 4 * (60 + 60 + 20 + 20) = 640 bytes
    e = counts.fused_eval(10, 20, 3, 1)
    assert e.flops == 2 * 1600 + 3200
    assert e.bytes == 2 * 480 + 640
    assert counts.fit_kmvp(10, 20, 3, 11).flops == 11 * e.flops


def test_fit_required_by_hand():
    # build: 2*10*20*3 + 2*20*20*3 = 1200 + 2400; per evaluation
    # 2 * 2*10*20 + 2*20*20 = 800 + 800; 11 evaluations
    w = counts.fit_required(10, 20, 3, 11)
    assert w.flops == 3600 + 11 * 1600
    assert w.bytes == 4 * (30 + 60 + 200 + 400) + 11 * 4 * (400 + 400)


def test_covtype_pass_is_compute_bound_on_v5e():
    p = peaks.peak("TPU v5 lite")
    w = counts.kmvp_pass(522_910, 16_384, 54, 1)
    assert w.flops == pytest.approx(9.4245e11, rel=1e-4)
    t, bound = counts.least_time_s(w, p.bf16_flops, p.hbm_bytes_s)
    assert bound == "compute"
    assert t == pytest.approx(w.flops / 197e12)


def test_peaks_v5e_row_and_unknown_kind():
    p = peaks.peak("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_s, p.hbm_bytes) == (197e12, 819e9,
                                                          16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("TPU v9 imaginary")

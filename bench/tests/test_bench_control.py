"""The control of ``correct``, at a size a test run holds: the reference
in three-pass bfloat16, put in the program's place, must come out not
correct on every seed, where the program comes out correct."""
import bench_tiny
import jax
import pytest

from bench import calibrate, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_tiny.make(tmp_path_factory.mktemp("bench"))


def _fails(numbers, limits):
    return any(numbers[k] > limits[k] for k in numbers)


@pytest.mark.parametrize("workload,seconds", [("covtype_otf.fit", 0.0),
                                              ("covtype_otf.serve_poisson",
                                               0.5)])
def test_control_fails_where_the_program_passes(root, workload, seconds):
    cell = spec.load_cell(workload, root)
    for seed in (2 ** 31 + 5, 21, 22):
        r = calibrate.readings(cell, seed, seconds, jax.devices()[:1])
        assert not _fails(r["program"], cell.limits), r
        assert _fails(r["control"], cell.limits), r

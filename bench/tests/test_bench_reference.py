"""The benchmark's copies of the paper references equal the originals, its
work model counts from shapes, and its bfloat16 control fails the limits
that the configurations set."""
import json
import os

import numpy as np
import pytest

from bench import reference
from benchmarks import paper_suite

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"filter_pipeline": 48, "fft": 2, "nbody": 600, "saxpy": 1000,
        "segmentation": 1}


@pytest.mark.parametrize("name", sorted(TINY))
def test_copies_equal_the_originals(name):
    size = TINY[name]
    ours = reference.make_inputs(name, size, 7)
    theirs = paper_suite.make_inputs(name, size, 7)
    assert ours.keys() == theirs.keys()
    for k in ours:
        np.testing.assert_array_equal(ours[k], theirs[k])
    want = paper_suite.reference(name, theirs)
    got = reference.reference(name, ours)
    assert got.keys() == want.keys()
    for k in got:
        np.testing.assert_array_equal(got[k], want[k])
    assert reference.TOLERANCE[name] == paper_suite.TOLERANCE[name]
    bent = {k: v + np.float32(0.5) for k, v in got.items()}
    assert reference.max_error(bent, want) == \
        paper_suite.max_error(bent, want) > 0


def test_max_error_refuses_a_wrong_shape_and_the_check_a_nan():
    want = {"z": np.zeros(4, np.float32)}
    assert reference.max_error({"z": np.zeros(3, np.float32)}, want) \
        == float("inf")
    nan = {"z": np.array([0, np.nan, 0, 0], np.float32)}
    assert reference.max_error(nan, want) == 0.0     # the copy's blind spot
    assert reference.checked_error(nan, want) == float("inf")
    assert reference.checked_error({"z": np.ones(4, np.float32)}, want) == 1.0


def test_unit_work_counts_bytes_from_shapes():
    # a filter line of 4096 px: 4 B read and 12 B written per pixel
    assert reference.unit_work("filter_pipeline", 4096) == \
        (7.0 * 4096, 16.0 * 4096)
    # a saxpy element: x and y read, z written
    assert reference.unit_work("saxpy", 10 ** 6) == (2.0, 12.0)
    # the minimum equals the bytes of the inputs and outputs themselves
    inputs = reference.make_inputs("filter_pipeline", 32, 0)
    outs = reference.reference("filter_pipeline", inputs)
    moved = sum(v.nbytes for v in inputs.values()) + \
        sum(v.nbytes for v in outs.values())
    assert 32 * reference.unit_work("filter_pipeline", 32)[1] == moved
    with pytest.raises(KeyError):
        reference.unit_work("nbody", 8192)


def _limit(config):
    with open(os.path.join(ROOT, "bench", "configs", config + ".json")) as f:
        return json.load(f)["max_err_limit"]


@pytest.mark.parametrize("config,sct,size", [
    ("filter-pipeline", "filter_pipeline", 256),
    ("saxpy", "saxpy", 100_000)])
def test_bfloat16_control_fails_the_limit(config, sct, size):
    for seed in (1, 2, 3):
        inputs = reference.make_inputs(sct, size, seed)
        want = reference.reference(sct, inputs)
        assert reference.max_error(want, want) == 0.0
        err = reference.max_error(reference.control(sct, inputs), want)
        assert err > 3 * _limit(config), (seed, err)

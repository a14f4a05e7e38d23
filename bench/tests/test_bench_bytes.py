"""The readers of ``ExecutionStats`` fields: the byte counts ``h2d_bytes``
and ``d2h_bytes``, the accelerator slot's phases ``accel_compute_s`` and
``accel_writeback_s``, and the admission wait ``admission_queue_s``.  On
made-up requests, on requests from a program that does not measure them,
and in a traced run of each cell at a tiny size on the CPU, where the
bytes are those of the split in effect and the phases lie inside the
slot."""
import json
import types

import jax
import pytest

from bench import load, run
from repro.core import AcceleratorPlatform, DeviceInfo

SPEC = run.load_spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
READERS = ("h2d_bytes", "d2h_bytes")
#: reader -> the ExecutionStats field it averages, for the timed readers
TIMED = {"accel_compute_s": "compute_a", "accel_writeback_s": "writeback_a",
         "admission_queue_s": "queue_seconds"}
TINY = {"filter_pipeline": 96, "saxpy": 20_000}
#: (h2d, d2h) bytes of a request with ``u`` accelerator units at size
#: ``n``: the filter's image rows in, its three outputs' rows back;
#: saxpy's x, y and the scalar a in, z back
BYTES = {"filter_pipeline": lambda u, n: (4 * u * n, 12 * u * n),
         "saxpy": lambda u, n: (8 * u + 4, 4 * u)}


def _ctx(stats, ok=None):
    reqs = []
    for k, st in enumerate(stats):
        r = load.Request(k, 0, 0.0)
        r.ok = True if ok is None else ok[k]
        r.stats = st
        reqs.append(r)
    return run.Context(requests=reqs, seconds=1.0, window_end=1.0,
                       setup_s=0.0, sct="saxpy", size=10, peaks={})


@pytest.mark.parametrize("name", READERS)
def test_reader_takes_the_mean_over_completed_requests(name):
    stats = [types.SimpleNamespace(**{name: v}) for v in (100, 300, 7)]
    ctx = _ctx(stats, ok=[True, True, False])
    assert run.reader(name)(ctx) == 200.0


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_counts_no_bytes(name):
    # the stats of a program without the counts, and no request at all
    assert run.reader(name)(_ctx([types.SimpleNamespace(time_a=0.1)])) \
        is None
    assert run.reader(name)(_ctx([])) is None


@pytest.mark.parametrize("name", sorted(TIMED))
def test_timed_reader_takes_the_mean_over_completed_requests(name):
    stats = [types.SimpleNamespace(**{TIMED[name]: v})
             for v in (0.25, 0.75, 9.0)]
    ctx = _ctx(stats, ok=[True, True, False])
    assert run.reader(name)(ctx) == pytest.approx(0.5)


@pytest.mark.parametrize("name", sorted(TIMED))
def test_timed_reader_finds_nothing_where_the_program_measures_nothing(
        name):
    assert run.reader(name)(_ctx([types.SimpleNamespace(time_a=0.1)])) \
        is None
    assert run.reader(name)(_ctx([])) is None


@pytest.mark.parametrize("workload", CELLS)
def test_a_traced_run_reads_the_bytes_of_its_split(workload, capsys):
    _, config, traffic = run.cell_files(SPEC, workload)
    n = TINY[config["sct"]]
    traffic = dict(traffic, size=n, sample_every=1,
                   settle={"quiet": 2, "cap_s": 5, "burst_s": 0.3})
    if traffic["loop"] == "open":
        traffic["rate"] = 10.0
    accel = AcceleratorPlatform([DeviceInfo("accel0", "accel",
                                            jax_device=jax.devices()[0])])
    got = run.measure(workload, 2 ** 31 + 13, 1.0, True, accel=accel,
                      devices=jax.devices()[:1], spec=SPEC, traffic=traffic)
    assert got["correct"], got["checks"]
    info = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # both SCTs have n domain units: image rows, vector elements
    share, = info["info"]["accel_share"]
    h2d, d2h = BYTES[config["sct"]](round(share * n), n)
    assert got["metrics"]["h2d_bytes"] == {"value": h2d, "unit": "B"}
    assert got["metrics"]["d2h_bytes"] == {"value": d2h, "unit": "B"}
    m = {k: v["value"] for k, v in got["metrics"].items()}
    assert 0 < m["accel_compute_s"] and 0 < m["accel_writeback_s"]
    assert m["accel_compute_s"] + m["accel_writeback_s"] <= \
        m["accel_slot_s"]
    assert ("admission_queue_s" in m) == (traffic["loop"] == "open")
    assert m.get("admission_queue_s", 0.0) >= 0.0

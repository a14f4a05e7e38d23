"""The harness at tiny sizes on the CPU: the spec and the files it names,
the traffic generator, the metric readers, the refusal to run without a
TPU, and whole runs whose ``correct`` follows the outputs: true on the
sound program, false under each fault a cell can have."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import load, run
from benchmarks import paper_suite
from repro.core import (AcceleratorPlatform, DeviceInfo, Map,
                        ThreadedExecutor, kernel, scalar, vector)

ROOT = run.ROOT
SPEC = run.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def test_spec_keys_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int) and \
        1 <= SPEC["run_seconds"] <= 51
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
        assert c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            names.append(m["name"])
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == \
            {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in SPEC[k]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in SPEC["workloads"]}) == \
        len(SPEC["workloads"])
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_every_config_has_a_cell_and_every_name_a_file():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    for w in SPEC["workloads"]:
        cell, config, traffic = run.cell_files(SPEC, w["name"])
        assert config["name"] == w["config"]
        assert traffic["size"] in config["sizes"]
        assert traffic["loop"] in ("closed", "open")
        assert config["dtype"] == "float32"
        assert config["accel_chips"] <= cell["chips"]
    for kind in ("end_to_end", "per_layer"):
        for m in SPEC[kind]:
            assert callable(run.reader(m["name"]))


def test_every_metric_cell_reports_what_the_metric_moves():
    cells = [w["name"] for w in SPEC["workloads"]]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for w in cells:
        got = {m["name"] for m in run.cell_metrics(SPEC, w, "end_to_end")}
        assert "setup_s" in got and len(got) >= 2
        assert run.cell_metrics(SPEC, w, "per_layer")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells), (m, w)


def test_unknown_device_kind_is_refused():
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.BenchError, match="no peaks"):
        run.load_peaks("TPU v9 imaginary")


def test_run_refuses_to_start_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    got = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         SPEC["workloads"][0]["name"],
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert got.returncode != 0
    assert got.stdout.strip() == ""
    assert "needs a TPU" in got.stderr


# ---------------------------------------------------------------------------
# Traffic and metrics
# ---------------------------------------------------------------------------

def test_open_schedule_repeats_for_a_seed_and_keeps_its_work():
    a = load.open_schedule(50.0, 10.0, 2 ** 31 + 7)
    np.testing.assert_array_equal(a, load.open_schedule(50.0, 10.0,
                                                        2 ** 31 + 7))
    b = load.open_schedule(50.0, 10.0, 8)
    assert len(a) == len(b) == 500
    assert not np.array_equal(a, b)
    # every seed gets the same gaps, in another order
    gaps = lambda t: np.sort(np.diff(np.append(t, 10.0)))  # noqa: E731
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9, atol=1e-12)
    assert a[0] == 0.0 and a[-1] < 10.0 and np.all(np.diff(a) >= 0)


def test_sample_offset_and_input_seeds_follow_the_seed():
    s = 2 ** 33 + 1
    assert load.sample_offset(s, 8) == load.sample_offset(s, 8)
    assert 0 <= load.sample_offset(s, 8) < 8
    assert load.input_seed(s, 0) != load.input_seed(s, 1)
    assert load.input_seed(s, 3) == load.input_seed(s, 3)


def _req(k, due, done, ok=True, span=0.0, submit=None):
    r = load.Request(k, 0, due)
    r.submit = due if submit is None else submit
    r.done, r.ok, r.span_s = done, ok, span
    return r


def test_percentiles_and_throughput_take_every_request_of_the_window():
    # 19 fast requests and one that failed late: it is in the tail
    reqs = [_req(k, float(k), k + 0.1) for k in range(19)]
    reqs.append(_req(19, 19.0, 29.0, ok=False))
    ctx = run.Context(requests=reqs, seconds=20.0, window_end=20.0,
                      setup_s=3.0, sct="saxpy", size=10, peaks={})
    lat = [0.1] * 19 + [10.0]
    assert run.reader("latency_p50_s")(ctx) == \
        pytest.approx(np.percentile(lat, 50))
    assert run.reader("latency_p95_s")(ctx) == \
        pytest.approx(np.percentile(lat, 95))
    assert run.reader("latency_p95_s")(ctx) > 0.5
    # completed inside the window only
    assert run.reader("throughput_rps")(ctx) == pytest.approx(19 / 20.0)
    reqs[18].done = 20.5
    assert run.reader("throughput_rps")(ctx) == pytest.approx(18 / 20.0)
    assert run.reader("setup_s")(ctx) == 3.0
    # admission wait: submit to result, less the node's span
    for r in reqs:
        r.span_s = 0.04
    assert run.reader("admission_wait_s")(ctx) == pytest.approx(
        np.mean([r.done - r.submit - 0.04 for r in reqs if r.ok]))
    # without a trace the device readers find nothing to read
    assert run.reader("device_idle_share")(ctx) is None
    assert run.reader("accel_roofline")(ctx) is None


# ---------------------------------------------------------------------------
# Whole runs on the CPU at tiny sizes
# ---------------------------------------------------------------------------

TINY = {"filter_pipeline": 96, "saxpy": 20_000}


def _accel(n=1):
    dev = jax.devices()[0]
    return AcceleratorPlatform([DeviceInfo(f"accel{i}", "accel",
                                           jax_device=dev)
                                for i in range(n)])


def _measure(workload, seed=2 ** 31 + 11, accel_devices=1):
    _, config, traffic = run.cell_files(SPEC, workload)
    traffic = dict(traffic, size=TINY[config["sct"]], sample_every=1,
                   settle={"quiet": 2, "cap_s": 5, "burst_s": 0.3})
    if traffic["loop"] == "open":
        traffic["rate"] = 10.0
    result = run.measure(workload, seed, 1.0, False,
                         accel=_accel(accel_devices),
                         devices=jax.devices()[:1], spec=SPEC,
                         traffic=traffic)
    json.dumps(result)           # the result line is plain JSON
    return result


@pytest.mark.parametrize("workload,accel_devices", [
    ("saxpy.1m.open", 1), ("saxpy.1m.open", 2),
    ("filter.4096.closed", 1), ("filter.4096.closed", 2)])
def test_a_sound_run_is_correct(workload, accel_devices):
    got = _measure(workload, accel_devices=accel_devices)
    assert got["correct"], got["checks"]
    assert got["failed"] == 0 and got["attempted"] > 0
    assert got["checks"]["compared"]["value"] >= 1
    assert list(got)[-1] == "checks"
    assert set(got["metrics"]) == {
        m["name"] for m in run.cell_metrics(SPEC, workload, "end_to_end")}
    assert got["device"]["count"] == 1


def _broken_saxpy(body):
    def build(n):
        return Map(kernel(body, name="saxpy",
                          inputs=[scalar("a"), vector("x", epu=1),
                                  vector("y", epu=1)],
                          outputs=[vector("z", epu=1)]))
    return build


def _drop_segment(monkeypatch, slot):
    """The executor claims a segment's outputs written and writes none."""
    real = ThreadedExecutor._direct_write

    def direct_write(self, out_env, seg, targets):
        if seg.slot == slot and targets:
            return frozenset(targets)
        return real(self, out_env, seg, targets)
    monkeypatch.setattr(ThreadedExecutor, "_direct_write", direct_write)


FAULTS = {
    # a stage that returns its input unchanged
    "unchanged": lambda mp, sct: (
        mp.setattr(paper_suite, "np_where_solarize", lambda x: x)
        if sct == "filter_pipeline" else
        mp.setitem(paper_suite.BENCHMARKS, "saxpy", (
            _broken_saxpy(lambda a, x, y: jnp.asarray(y)),
            *paper_suite.BENCHMARKS["saxpy"][1:]))),
    # one answer altered where it is produced
    "altered": lambda mp, sct: (
        mp.setattr(paper_suite, "np_where_solarize",
                   lambda x: jnp.where(x > 128.0, 255.0 - x, x)
                   .at[0, 0].add(1.0))
        if sct == "filter_pipeline" else
        mp.setitem(paper_suite.BENCHMARKS, "saxpy", (
            _broken_saxpy(
                lambda a, x, y: (jnp.multiply(a, x) + y).at[0].add(1.0)),
            *paper_suite.BENCHMARKS["saxpy"][1:]))),
    # the host class's part of the batch left out of the result
    "host_part_left_out": lambda mp, sct: _drop_segment(mp, 1),
}


CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_makes_the_run_incorrect(monkeypatch, workload, fault):
    _, config, _ = run.cell_files(SPEC, workload)
    FAULTS[fault](monkeypatch, config["sct"])
    got = _measure(workload)
    assert not got["correct"], got["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_a_chip_left_out_of_the_exchange_makes_the_run_incorrect(
        monkeypatch, workload):
    # two accelerator slots: the second one's segment never reaches the
    # merged result
    _drop_segment(monkeypatch, 1)
    got = _measure(workload, accel_devices=2)
    assert not got["correct"], got["checks"]


def _bf16_filter(width):
    """The paper's filter pipeline with every stage computed in bfloat16
    (outputs handed back as float32)."""
    from repro.core import Pipeline
    from repro.core.spec import Trait
    bf16, f32 = jnp.bfloat16, jnp.float32

    def noise(img, row0):
        rows = row0 + jnp.arange(img.shape[0])
        h = (rows[:, None] * 31 + jnp.arange(img.shape[1])[None, :] * 17) % 13
        x = img.astype(bf16)
        return jnp.clip(x + (h.astype(bf16) - bf16(6)), 0, 255).astype(f32)

    def solarize(x):
        x = x.astype(bf16)
        return jnp.where(x > 128, bf16(255) - x, x).astype(f32)

    return Pipeline(
        kernel(noise, name="gauss_noise",
               inputs=[vector("img", epu=1),
                       scalar("row0", trait=Trait.OFFSET)],
               outputs=[vector("noisy", epu=1)]),
        kernel(solarize, name="solarize", inputs=[vector("noisy", epu=1)],
               outputs=[vector("sol", epu=1)]),
        kernel(lambda x: x[:, ::-1], name="mirror",
               inputs=[vector("sol", epu=1)], outputs=[vector("out", epu=1)]))


#: the reference's arithmetic one precision down (bfloat16 for the
#: configurations' float32), put in the program's place
BF16_CONTROL = {
    "filter_pipeline": _bf16_filter,
    "saxpy": _broken_saxpy(lambda a, x, y: (
        jnp.asarray(a, jnp.bfloat16) * x.astype(jnp.bfloat16)
        + y.astype(jnp.bfloat16)).astype(jnp.float32)),
}


@pytest.mark.parametrize("workload", CELLS)
def test_the_bfloat16_control_in_the_programs_place_is_incorrect(
        monkeypatch, workload):
    _, config, _ = run.cell_files(SPEC, workload)
    sct = config["sct"]
    monkeypatch.setitem(paper_suite.BENCHMARKS, sct, (
        BF16_CONTROL[sct], *paper_suite.BENCHMARKS[sct][1:]))
    got = _measure(workload)
    assert not got["correct"], got["checks"]
    assert got["failed"] == 0
    assert got["checks"]["missing"]["value"] == 0
    assert got["checks"]["max_err"]["value"] > \
        3 * got["checks"]["max_err"]["limit"]

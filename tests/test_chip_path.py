"""The chip path on the CPU: slots bound to real ``jax.Device``s, the
paper SCTs against their float32 references, the ``chip_smoke.py`` phases
at tiny sizes, and the compile-cache helper.

Paths that need several devices run in a child process with four virtual
CPU devices, so this process keeps its single device."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

import chip_smoke
from benchmarks.paper_suite import (BENCHMARKS, TOLERANCE,
                                    filter_pipeline_sct, make_inputs,
                                    max_error, reference)
from repro.core import (AcceleratorPlatform, DeviceInfo, HostPlatform,
                        KnowledgeBase, Scheduler, ThreadedExecutor)
from repro.jaxcache import CHECKOUT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = {"filter_pipeline": 64, "fft": 2, "nbody": 64, "saxpy": 1000,
        "segmentation": 2}
TINY_KERNELS = {"saxpy": 1000, "filter_pipeline": 64, "segmentation": 1,
                "nbody": 64}


def accel_platform(devices):
    """The accelerator class over CPU devices: classed ``accel`` so the
    scheduler keeps them out of the host class, which is kind ``cpu``."""
    return AcceleratorPlatform([DeviceInfo(f"accel{d.id}", "accel",
                                           jax_device=d) for d in devices])


def _child(code: str, *, devices: int = 1, env=None, cwd=ROOT,
           timeout: float = 300) -> subprocess.CompletedProcess:
    full = {**os.environ, "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": os.pathsep.join([os.path.join(ROOT, "src"), ROOT]),
            **(env or {})}
    if devices > 1:
        full["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          cwd=cwd, env=full, capture_output=True, text=True,
                          timeout=timeout)


# ---------------------------------------------------------------------------
# platforms built from jax.devices()
# ---------------------------------------------------------------------------

def test_from_jax_records_devices_and_leaves_peaks_unknown():
    dev = jax.devices()[0]
    accel = AcceleratorPlatform.from_jax([dev, dev])
    host = HostPlatform.from_jax()
    for info in accel.devices:
        assert info.jax_device is dev and info.kind == dev.platform
        assert info.name == f"{dev.platform}{dev.id}"
        assert info.peak_flops is None and info.hbm_bw is None
    assert accel.calibrate() == [0.5, 0.5]
    assert host.device.kind == "cpu"
    assert host.device.compute_units == (os.cpu_count() or 1)
    assert host.device.jax_device is jax.devices("cpu")[0]
    # hand-built entries keep the analytic split and no binding
    legacy = AcceleratorPlatform(
        [DeviceInfo("g0", "gpu"), DeviceInfo("g1", "gpu", peak_flops=591e12)])
    assert legacy.calibrate() == pytest.approx([0.25, 0.75])
    assert legacy.devices[0].jax_device is None


# ---------------------------------------------------------------------------
# filter pipeline: a split run equals the whole image
# ---------------------------------------------------------------------------

def test_filter_pipeline_split_equals_whole_image():
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=4),
                        topology={"L2": 2, "NO_FISSION": 1})
    accel = AcceleratorPlatform([DeviceInfo("gpu0", "gpu")], max_overlap=2)
    sched = Scheduler(host=host, accel=accel, executor=ThreadedExecutor(),
                      kb=KnowledgeBase(), default_share_a=0.55)
    inputs = make_inputs("filter_pipeline", 64, seed=3)
    run = sched.run(filter_pipeline_sct(64), inputs)
    # more than one slot, and a boundary off the noise period of 13 rows
    units = run.node_plan.part.units
    assert len([u for u in units if u]) > 1
    assert any(np.cumsum(units)[:-1] % 13)
    assert max_error(run.outputs, reference("filter_pipeline", inputs)) == 0
    sched.close()


# ---------------------------------------------------------------------------
# bytes handed between host memory and the accelerator class
# ---------------------------------------------------------------------------

#: (h2d, d2h) bytes of one request with ``u`` accelerator units at size
#: ``n``: the filter's image rows in and its three outputs' rows back;
#: saxpy's x, y and the float32 scalar a in and z back
HANDED_BYTES = {"filter_pipeline": lambda u, n: (4 * u * n, 3 * 4 * u * n),
                "saxpy": lambda u, n: (8 * u + 4, 4 * u)}


def _bytes_scheduler(health=None):
    from repro.core import Telemetry
    telemetry = Telemetry()
    sched = Scheduler(host=HostPlatform.from_jax(),
                      accel=accel_platform(jax.devices()[:1]),
                      executor=ThreadedExecutor(), kb=KnowledgeBase(),
                      health=health, telemetry=telemetry)
    return sched, telemetry


@pytest.mark.parametrize("name", sorted(HANDED_BYTES))
def test_bytes_handed_to_and_from_the_accelerator_follow_the_split(name):
    n = TINY[name]
    sched, telemetry = _bytes_scheduler()
    sct = BENCHMARKS[name][0](n)
    # the first run learns the output shapes and copies in the merge; the
    # second writes each slot's outputs straight into the host buffers
    runs = [sched.run(sct, make_inputs(name, n, seed=s)) for s in (1, 2)]
    sched.close()
    assert runs[0].stats.merge_bytes > 0 and runs[1].stats.merge_bytes == 0
    for run in runs:
        part = run.node_plan.part
        u = sum(k for s, k in zip(part.slots, part.units)
                if s.device_type != "cpu")
        assert 0 < u < sum(part.units)
        h2d, d2h = HANDED_BYTES[name](u, n)
        assert (run.stats.h2d_bytes, run.stats.d2h_bytes) == (h2d, d2h)
    metrics = telemetry.metrics.snapshot()
    assert metrics["h2d_bytes_total"] == 2 * h2d
    assert metrics["d2h_bytes_total"] == 2 * d2h


@pytest.mark.parametrize("name", sorted(HANDED_BYTES))
def test_no_bytes_are_handed_when_every_unit_runs_on_the_host(name):
    from repro.core.faults import DeviceHealth
    health = DeviceHealth(quarantine_after=1, probe_after=10 ** 6)
    health.record_failure("accel0")
    sched, telemetry = _bytes_scheduler(health)
    n = TINY[name]
    run = sched.run(BENCHMARKS[name][0](n), make_inputs(name, n))
    sched.close()
    assert {s.device_type for s in run.node_plan.part.slots} == {"cpu"}
    assert (run.stats.h2d_bytes, run.stats.d2h_bytes) == (0, 0)
    assert telemetry.metrics.snapshot()["h2d_bytes_total"] == 0


def test_the_accelerator_slot_time_splits_into_compute_and_writeback():
    from repro.core import kernel, vector

    def wait(v):
        time.sleep(0.1)
        return v

    def slow(x):
        # a host callback inside the compiled body: every run waits
        x = jax.pure_callback(wait, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return x * 2.0
    sct = kernel(slow, name="slow_double", inputs=[vector("x")],
                 outputs=[vector("z")])
    sched, telemetry = _bytes_scheduler()
    x = np.arange(4096, dtype=np.float32)
    # the second run writes the chip's output straight into a host buffer
    runs = [sched.run(sct, {"x": x}) for _ in range(2)]
    sched.close()
    for run in runs:
        st = run.stats
        assert st.compute_a >= 0.1 and st.writeback_a >= 0.0
        assert st.compute_a + st.writeback_a <= st.time_a
        assert st.compute_a + st.writeback_a == pytest.approx(st.time_a,
                                                              abs=0.01)
    assert runs[1].stats.writeback_a > 0.0
    assert runs[1].stats.d2h_bytes > 0
    # the write-back span notes the device→host copies it waited for
    open_spans, notes = {}, []
    for e in telemetry.tracer.events():
        if e["name"] != "writeback":
            continue
        if e["ph"] == "B":
            open_spans[e["tid"]] = e["args"]["cls"]
        elif e["ph"] == "E" and open_spans.pop(e["tid"]) == "a":
            notes.append(e["args"]["blocks"])
    assert len(notes) == 2 and notes[1] >= 1
    assert runs[1].stats.d2h_blocks == notes[1]


# ---------------------------------------------------------------------------
# chip_smoke.py phases at tiny sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_session():
    session, telemetry = chip_smoke.build_session(
        accel_platform(jax.devices()[:1]))
    yield session, telemetry
    session.shutdown()


@pytest.mark.parametrize("name", sorted(TINY))
def test_smoke_sct_phase(name, smoke_session):
    session, telemetry = smoke_session
    rec = chip_smoke.sct_phase(session, telemetry, name, TINY[name])
    assert rec["max_err"] <= TOLERANCE[name]
    assert rec["requests"] == chip_smoke.REQUESTS
    assert min(rec["accel_share"]) > 0
    assert set(rec["slots"]) == {"accel0/q0", "host/f0"}


@pytest.mark.parametrize("name", sorted(TINY_KERNELS))
def test_smoke_kernel_phase(name):
    dev = jax.devices()[0]
    rec = chip_smoke.kernel_phase(name, TINY_KERNELS[name], dev,
                                  interpret=True)
    assert rec["max_err"] <= chip_smoke.KERNEL_TOLERANCE[name]
    assert rec["device"] == str(dev)


def test_smoke_placement_check_rejects_foreign_outputs(smoke_session):
    session, telemetry = smoke_session
    telemetry.tracer.clear()
    with telemetry.tracer.span("slot", device="accel0/q0") as sp:
        sp.note(bound=str(jax.devices()[0]), placed=["host"])
    with pytest.raises(chip_smoke.SmokeFailure, match="left outputs"):
        chip_smoke.check_placement(session, telemetry)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_script_fails_without_tpu(where, tmp_path):
    script = os.path.join(ROOT, "chip_smoke.py")
    cwd = ROOT
    if where == "alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
        cwd = tmp_path
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


# ---------------------------------------------------------------------------
# four virtual devices: slot binding and the --chips 4 path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def four_devices():
    proc = _child("""
        import json, jax
        import chip_smoke
        from benchmarks.paper_suite import make_inputs, saxpy_sct
        from repro.core import AcceleratorPlatform, DeviceInfo, JobGraph
        def accel_platform(devices):
            return AcceleratorPlatform([DeviceInfo(f"accel{d.id}", "accel",
                                                   jax_device=d)
                                        for d in devices])
        devs = jax.devices()
        session, tel = chip_smoke.build_session(accel_platform(devs))
        with session:
            graph = JobGraph()
            graph.add(saxpy_sct())
            session.submit(graph, **make_inputs("saxpy", 4096)).result(120)
            spans = chip_smoke.slot_devices(tel)
        print(json.dumps({"devices": [str(d) for d in devs],
                          "cpu": str(jax.devices("cpu")[0]),
                          "spans": spans}))
        print(json.dumps(chip_smoke.four_chip_phase(
            "filter_pipeline", 64, accel_platform(devs[:4]))))
    """, devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_each_accelerator_slot_outputs_on_its_own_device(four_devices):
    binding, _ = four_devices
    accel = [(s, b, p) for s, b, p in binding["spans"]
             if s.startswith("accel")]
    assert sorted(b for _, b, _ in accel) == sorted(binding["devices"])
    for slot, bound, placed in accel:
        assert placed == [bound], slot
        assert bound == binding["devices"][int(slot[5:slot.index("/")])]
    host = [(b, p) for s, b, p in binding["spans"] if s.startswith("host/")]
    assert host == [(binding["cpu"], [binding["cpu"]])]


def test_four_device_leg_matches_one_device_leg(four_devices):
    _, rec = four_devices
    assert len(rec["accel_devices"]) == 4
    assert rec["cross_err"] == 0
    assert rec["wide"]["max_err"] == 0 and rec["chip0"]["max_err"] == 0


# ---------------------------------------------------------------------------
# compile cache placed from outside
# ---------------------------------------------------------------------------

CACHE_PROBE = """
    import jax
    from repro.jaxcache import use_compile_cache
    print(use_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_defaults_to_checkout():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(CACHE_PROBE)], cwd="/",
        env={**env, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.join(ROOT, "src")},
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    want = os.path.join(CHECKOUT, ".jax_cache")
    assert proc.stdout.split() == [want, want]


def test_compile_cache_env_wins(tmp_path):
    proc = _child(CACHE_PROBE,
                  env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [str(tmp_path), str(tmp_path)]

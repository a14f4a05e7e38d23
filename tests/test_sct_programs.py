"""Each slot runs its SCT body as one compiled program per (SCT object,
device, segment shape): outputs equal the eager body and the float32
references bit for bit, and compiles happen only for shapes not seen."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference
from benchmarks.paper_suite import BENCHMARKS, TOLERANCE, filter_pipeline_sct
from repro.core import (AcceleratorPlatform, DeviceInfo, HostPlatform,
                        KnowledgeBase, LoadBalancer, Scheduler, Telemetry,
                        ThreadedExecutor, kernel, vector)
from repro.core.skeletons import PartitionInfo

FILTER_OUTPUTS = ("noisy", "sol", "out")
TINY = {"filter_pipeline": 64, "fft": 2, "nbody": 64, "saxpy": 1000,
        "segmentation": 2}


def _scheduler(share: float = 0.8):
    """The benchmark's Session layout on the CPU: an accelerator class
    over JAX's CPU device, the host class beside it, the split held."""
    telemetry = Telemetry()
    # classed "accel": the scheduler takes kind "cpu" for the host class
    accel = AcceleratorPlatform([DeviceInfo("accel0", "accel",
                                            jax_device=jax.devices()[0])])
    sched = Scheduler(host=HostPlatform.from_jax(), accel=accel,
                      executor=ThreadedExecutor(), kb=KnowledgeBase(),
                      balancer=LoadBalancer(trigger=float("inf")),
                      default_share_a=share, telemetry=telemetry)
    return sched, telemetry


def _counts(telemetry):
    snap = telemetry.metrics.snapshot()
    return (snap.get("sct_programs_compiled_total", 0),
            snap.get("sct_program_runs_total", 0))


def _segments(run):
    """(start, units) of every slot's segment (a slot with no units runs
    its body over an empty segment)."""
    return list(run.node_plan.part.layout())


# ---------------------------------------------------------------------------
# the compiled body against the eager one and the references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,offset", [(64, 0), (32, 32), (27, 37)])
def test_compiled_filter_segment_equals_the_eager_body(rows, offset):
    img = reference.make_inputs("filter_pipeline", 64, seed=5)["img"]
    seg = img[offset:offset + rows]
    sct = filter_pipeline_sct(64)
    env = {"img": seg, "__partition__": PartitionInfo(size=rows,
                                                      offset=offset)}
    eager = sct.apply(dict(env))
    compiled, fresh = sct.compiled_apply(env)
    want = reference.reference("filter_pipeline", {"img": img})
    assert fresh
    assert compiled["img"] is seg            # inputs stay as they were
    for name in FILTER_OUTPUTS:
        got = np.asarray(compiled[name])
        np.testing.assert_array_equal(got, np.asarray(eager[name]))
        # the mirror flips columns only, so every output is row-local
        np.testing.assert_array_equal(got, want[name][offset:offset + rows])


@pytest.mark.parametrize("share", [0.5, 0.55, 0.8])
def test_filter_through_the_executor_is_bit_identical(share):
    """Even and uneven splits, every slot past the first at a nonzero
    OFFSET, give the whole image's outputs exactly."""
    n = 64
    sched, _ = _scheduler(share)
    sct = filter_pipeline_sct(n)
    inputs = reference.make_inputs("filter_pipeline", n, seed=7)
    try:
        runs = [sched.run(sct, inputs) for _ in range(2)]
    finally:
        sched.close()
    segs = _segments(runs[0])
    assert len(segs) == 2 and segs[1][0] > 0
    want = reference.reference("filter_pipeline", inputs)
    eager = sct.apply({"img": jnp.asarray(inputs["img"])})
    for run in runs:
        for name in FILTER_OUTPUTS:
            np.testing.assert_array_equal(run.outputs[name], want[name])
            np.testing.assert_array_equal(run.outputs[name],
                                          np.asarray(eager[name]))


# ---------------------------------------------------------------------------
# how often a program is compiled
# ---------------------------------------------------------------------------

def test_a_new_offset_of_the_same_shape_adds_no_compile():
    sct = filter_pipeline_sct(64)
    img = reference.make_inputs("filter_pipeline", 64, seed=1)["img"]
    fresh = [sct.compiled_apply({"img": img[off:off + 16],
                                 "__partition__": PartitionInfo(16, off)})[1]
             for off in (0, 16, 13, 48)]
    assert fresh == [True, False, False, False]
    # a new shape compiles once, then is served from its program
    fresh = [sct.compiled_apply({"img": img[:20],
                                 "__partition__": PartitionInfo(20, 3)})[1]
             for _ in range(2)]
    assert fresh == [True, False]


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_a_second_request_of_the_same_shape_adds_no_compile(name):
    """Every SCT of the paper suite traces as one program (N-body's Loop
    included), compiled in the first request and never again."""
    n = TINY[name]
    sched, telemetry = _scheduler()
    sct = BENCHMARKS[name][0](n)
    try:
        first = sched.run(sct, reference.make_inputs(name, n, seed=1))
        after_first = _counts(telemetry)
        second = sched.run(sct, reference.make_inputs(name, n, seed=2))
    finally:
        sched.close()
    slots = len(_segments(first))
    assert _segments(second) == _segments(first)
    assert 1 <= after_first[0] <= slots
    assert after_first[1] == slots
    assert _counts(telemetry) == (after_first[0], 2 * slots)
    want = reference.reference(name, reference.make_inputs(name, n, seed=2))
    assert reference.max_error(second.outputs, want) <= TOLERANCE[name]


def test_a_new_shape_adds_exactly_one_compile_per_new_segment_shape():
    sched, telemetry = _scheduler(0.8)
    sct = filter_pipeline_sct(64)
    try:
        first = sched.run(sct, reference.make_inputs("filter_pipeline", 64))
        c1, r1 = _counts(telemetry)
        sched.run(sct, reference.make_inputs("filter_pipeline", 64, seed=3))
        assert _counts(telemetry) == (c1, r1 + 2)
        bigger = sched.run(sct,
                           reference.make_inputs("filter_pipeline", 72))
        c3, r3 = _counts(telemetry)
    finally:
        sched.close()
    # two slots, one device here: one program per distinct segment shape
    shapes = {u for _, u in _segments(first)}
    new_shapes = {u for _, u in _segments(bigger)} - shapes
    assert (c1, r1) == (len(shapes), 2)
    assert (c3, r3) == (c1 + len(new_shapes), r1 + 4)
    assert len(new_shapes) == 2


def test_the_compute_span_notes_whether_the_slot_compiled():
    sched, telemetry = _scheduler(0.8)
    sct = filter_pipeline_sct(64)
    try:
        for seed in (1, 2):
            sched.run(sct, reference.make_inputs("filter_pipeline", 64,
                                                 seed=seed))
    finally:
        sched.close()
    notes = [e["args"]["compiled"] for e in telemetry.tracer.events()
             if e["name"] == "compute" and e["ph"] == "E"]
    assert notes == [True, True, False, False]


def test_identical_structures_with_other_kernels_never_share_a_program():
    """The program belongs to the SCT object, not to its structural id:
    two trees that differ only in their kernel functions compile apart
    and each computes its own kernel."""
    plus = [kernel(lambda x, c=c: x + c, name="shift",
                   inputs=[vector("x", epu=1)],
                   outputs=[vector("z", epu=1)]) for c in (1.0, 2.0)]
    assert plus[0].unique_id() == plus[1].unique_id()
    sched, telemetry = _scheduler(0.8)
    x = np.arange(64, dtype=np.float32)
    try:
        runs, outs = [], []
        for sct in plus:
            for _ in range(2):
                runs.append(sched.run(sct, {"x": x}))
                # the next run may reuse the output buffer: copy it now
                outs.append(np.array(runs[-1].outputs["z"]))
    finally:
        sched.close()
    for out, c in zip(outs, (1.0, 1.0, 2.0, 2.0)):
        np.testing.assert_array_equal(out, x + np.float32(c))
    slots = len(_segments(runs[0]))
    assert _counts(telemetry) == (2 * slots, 4 * slots)

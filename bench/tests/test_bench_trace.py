"""The trace reduction behind ``device_idle_share`` and
``accel_roofline``: union-of-intervals arithmetic, on made-up
overlapping events and on a profiler trace recorded here on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import devtrace


def test_merge_joins_overlapping_and_touching_events():
    evs = [(20, 30), (0, 10), (5, 15), (30, 31), (40, 40), (12, 14)]
    assert devtrace.merge(evs) == [(0, 15), (20, 31)]


def test_union_counts_overlap_once_and_clips_to_the_window():
    evs = [(0, 10), (5, 15), (5, 15), (20, 30), (28, 45)]
    assert devtrace.union_length(evs, 0, 100) == 15 + 25
    # the window cuts the first and the last run of busy time
    assert devtrace.union_length(evs, 8, 40) == 7 + 20
    assert devtrace.union_length(evs, 16, 19) == 0


def test_gaps_are_the_window_less_its_busy_time():
    busy = devtrace.merge([(2, 4), (3, 6), (8, 9)])
    assert devtrace.gaps(busy, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert devtrace.gaps(busy, 3, 5) == []
    got = devtrace.gaps(busy, 0, 10)
    assert sum(e - s for s, e in got) + \
        devtrace.union_length(busy, 0, 10) == 10


def _brute_busy(evs, lo, hi, step=1000):
    """Busy time by a timeline at ``step`` ns: the same union, computed
    without sorting or merging."""
    grid = np.zeros(int((hi - lo) // step) + 1, bool)
    for s, e in evs:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) // step):int(np.ceil((b - lo) / step))] = True
    return grid.sum() * step


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A real trace: a few XLA programs on the CPU from two threads at
    once (so op events overlap across lines), inside the window mark."""
    import threading
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    x = jnp.ones((256, 256))

    def work():
        y = x
        for _ in range(20):
            y = jnp.tanh(y @ x)
        y.block_until_ready()
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        ts = [threading.Thread(target=work) for _ in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    jax.profiler.stop_trace()
    # the CPU backend runs its ops on host threads: read those as the
    # "device" lines, which is what the reduction does with a TPU's
    return devtrace.Trace(devtrace.find_xplane(d),
                          device_plane=devtrace.HOST_PLANE,
                          op_lines=("tf_XLA",))


def test_recorded_trace_reduces_to_its_union(cpu_trace):
    lo, hi = cpu_trace.window
    assert hi > lo
    (plane, evs), = cpu_trace.ops.items()
    spans = [(s, e) for s, e, _ in evs if e > s]
    assert spans, "no op events recorded"
    busy = cpu_trace.busy_s()[plane] * 1e9
    assert 0 < busy <= hi - lo
    assert busy <= sum(min(e, hi) - max(s, lo) for s, e in spans
                       if e > lo and s < hi) + 1e-6
    brute = _brute_busy(spans, lo, hi)
    # the timeline rounds each merged run out to whole steps
    assert abs(brute - busy) <= 2000 * (len(spans) + 1)
    gap_s = sum(s for _, s in cpu_trace.idle_gaps(top=10 ** 6))
    assert gap_s * 1e9 + busy == pytest.approx(hi - lo, rel=1e-9)
    ops = cpu_trace.device_ops(top=10 ** 6)
    assert ops and all(sec > 0 for _, sec in ops)
    assert sum(sec for _, sec in ops) * 1e9 >= busy - 1e-3

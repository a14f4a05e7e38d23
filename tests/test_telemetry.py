"""Telemetry subsystem (ISSUE 8): tracing, metrics, events, integration.

Covers the primitives (nested spans → Chrome B/E pairs, virtual-clock
spans, metrics registry + Prometheus dump, event ring buffer + logging
bridge), the no-op fast path (microbench bound), the instrumented
pipeline (run/plan/dispatch/slot/merge spans, fault + repartition
events, quarantine warnings), the Session surface
(``metrics``/``counters``/``export_trace``), determinism under the
simulator, and the ``ExecutionStats.overhead_seconds`` invariants
satellite.
"""
import itertools
import json
import logging
import math
import threading
import time

import numpy as np
import pytest

from repro.core import (AcceleratorPlatform, DeviceInfo, FaultInjector,
                        FaultPolicy, HostPlatform, KnowledgeBase,
                        LoadBalancer, NULL_TELEMETRY, PlatformConfig, Profile,
                        Scheduler, Session, SimDevice, SimulatedExecutor,
                        Telemetry, ThreadedExecutor, Tracer,
                        validate_chrome_trace)
from repro.core.faults import DeviceHealth
from repro.core.load_balancer import ExecutionStats
from repro.core.telemetry import (EventLog, MetricsRegistry, metrics_block)
from repro.core import kernel, scalar, vector

POLICY = FaultPolicy(watchdog_multiple=1e6)   # no spurious watchdog on CI


def counting_clock(step: float = 1.0):
    c = itertools.count()
    return lambda: next(c) * step


def saxpy_tree():
    return kernel(lambda a, x, y: a * x + y, name="saxpy",
                  inputs=[scalar("a"), vector("x"), vector("y")],
                  outputs=[vector("z")])


def chain_trees():
    k2 = kernel(lambda a, z: z * a, name="scale",
                inputs=[scalar("a"), vector("z")], outputs=[vector("w")])
    return [saxpy_tree(), k2]


def saxpy_arrays(n=256, a=2.0):
    return {"a": np.float32(a),
            "x": np.arange(n, dtype=np.float32),
            "y": np.ones(n, dtype=np.float32)}


def make_scheduler(executor, **kw):
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=4),
                        topology={"L2": 2, "NO_FISSION": 1})
    accel = AcceleratorPlatform([DeviceInfo("gpu0", "gpu")], max_overlap=2)
    kw.setdefault("balancer", LoadBalancer(max_dev=0.0))
    kw.setdefault("kb", KnowledgeBase())
    return Scheduler(host=host, accel=accel, executor=executor, **kw)


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nested_spans_emit_matched_be_pairs(self):
        tr = Tracer(clock=counting_clock())
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        evs = tr.events()
        assert [(e["name"], e["ph"]) for e in evs] == \
            [("outer", "B"), ("inner", "B"), ("inner", "E"), ("outer", "E")]
        assert all(e["ts"] >= 0 for e in evs)

    def test_span_attrs_and_late_notes(self):
        tr = Tracer(clock=counting_clock())
        with tr.span("plan", slots=3) as sp:
            sp.note(cache_hit=True)
        b, e = tr.events()
        assert b["args"] == {"slots": 3}
        assert e["args"] == {"cache_hit": True}

    def test_exception_annotates_and_closes_span(self):
        tr = Tracer(clock=counting_clock())
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        b, e = tr.events()
        assert e["ph"] == "E" and e["args"]["error"] == "ValueError"
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_instant_and_virtual_record(self):
        tr = Tracer(clock=counting_clock())
        tr.instant("marker", reason="test")
        tr.record("slot", 100.0, 50.0, tid=7, device="gpu0")
        inst, x = tr.events()
        assert inst["ph"] == "i"
        assert x == {"name": "slot", "ph": "X", "ts": 100.0, "dur": 50.0,
                     "pid": 0, "tid": 7, "args": {"device": "gpu0"}}
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_threads_get_distinct_tids(self):
        tr = Tracer()
        # all three threads are alive at once, so none reuses another's
        # thread ident
        together = threading.Barrier(3)

        def spin():
            with tr.span("t"):
                together.wait(10)

        threads = [threading.Thread(target=spin) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        tids = {e["tid"] for e in tr.events()}
        assert len(tids) == 3
        assert validate_chrome_trace(tr.chrome_trace()) == []

    def test_open_spans_closed_at_export(self):
        tr = Tracer(clock=counting_clock())
        sp = tr.span("dangling")
        sp.__enter__()                       # never exited
        trace = tr.chrome_trace()
        assert validate_chrome_trace(trace) == []
        closing = trace["traceEvents"][-1]
        assert closing["ph"] == "E" and closing["args"]["unterminated"]

    def test_capacity_bound_drops_excess(self):
        tr = Tracer(clock=counting_clock(), capacity=4)
        for _ in range(5):
            with tr.span("s"):
                pass
        assert len(tr.events()) == 4
        assert tr.dropped == 6


# ---------------------------------------------------------------------------
# Chrome-trace validation
# ---------------------------------------------------------------------------

class TestValidation:
    def test_detects_unmatched_b(self):
        trace = {"traceEvents": [
            {"name": "a", "ph": "B", "ts": 0, "pid": 0, "tid": 0}]}
        assert any("unmatched B" in e for e in validate_chrome_trace(trace))

    def test_detects_mismatched_nesting(self):
        trace = {"traceEvents": [
            {"name": "a", "ph": "B", "ts": 0, "pid": 0, "tid": 0},
            {"name": "b", "ph": "E", "ts": 1, "pid": 0, "tid": 0}]}
        assert any("mismatched" in e for e in validate_chrome_trace(trace))

    def test_detects_missing_keys_and_bad_x(self):
        trace = {"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "pid": 0, "tid": 0},
            {"ph": "i", "ts": 0, "pid": 0, "tid": 0}]}
        errs = validate_chrome_trace(trace)
        assert any("dur" in e for e in errs)
        assert any("missing keys" in e for e in errs)

    def test_rejects_non_object(self):
        assert validate_chrome_trace([]) == ["trace is not a JSON object"]
        assert validate_chrome_trace({}) == \
            ["traceEvents missing or not a list"]


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class TestMetrics:
    def test_counter_inc_and_snapshot(self):
        reg = MetricsRegistry()
        reg.counter("runs_total").inc()
        reg.counter("runs_total").inc(2)
        assert reg.snapshot() == {"runs_total": 3.0}

    def test_labelled_series_are_distinct(self):
        reg = MetricsRegistry()
        reg.counter("busy", device="gpu0").inc(1.5)
        reg.counter("busy", device="cpu0").inc(0.5)
        snap = reg.snapshot()
        assert snap["busy{device=gpu0}"] == 1.5
        assert snap["busy{device=cpu0}"] == 0.5

    def test_gauge_and_histogram(self):
        reg = MetricsRegistry()
        reg.gauge("lbt").set(0.75)
        h = reg.histogram("lat", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        snap = reg.snapshot()
        assert snap["lbt"] == 0.75
        assert snap["lat"]["count"] == 3
        assert snap["lat"]["sum"] == pytest.approx(5.55)
        assert snap["lat"]["buckets"] == {"0.1": 1, "1.0": 2, "+Inf": 3}

    def test_prometheus_dump(self):
        reg = MetricsRegistry()
        reg.counter("runs_total", status="ok").inc(4)
        reg.histogram("lat", buckets=(1.0,)).observe(0.5)
        text = reg.to_prometheus()
        assert "# TYPE runs_total counter" in text
        assert 'runs_total{status="ok"} 4.0' in text
        assert 'lat_bucket{le="1.0"} 1' in text
        assert "lat_count 1" in text


# ---------------------------------------------------------------------------
# Event log
# ---------------------------------------------------------------------------

class TestEventLog:
    def test_ring_buffer_capacity(self):
        log = EventLog(capacity=3, bridge=False)
        for i in range(5):
            log.emit("e", i=i)
        assert [e.fields["i"] for e in log.records()] == [2, 3, 4]
        assert log.records()[-1].seq == 4

    def test_sink_called_and_broken_sink_contained(self):
        seen = []
        log = EventLog(bridge=False, sink=seen.append)
        log.add_sink(lambda e: 1 / 0)     # must not propagate
        ev = log.emit("fault", device="gpu0")
        assert seen == [ev]
        assert ev.fields == {"device": "gpu0"}

    def test_kind_prefix_filter(self):
        log = EventLog(bridge=False)
        log.emit("health.quarantined")
        log.emit("health.reinstated")
        log.emit("fault")
        assert len(log.records("health")) == 2

    def test_logging_bridge(self, caplog):
        log = EventLog()
        with caplog.at_level(logging.INFO, logger="repro.telemetry"):
            log.emit("balancer.trigger", level="info", lbt=0.95)
        assert any("balancer.trigger" in r.message for r in caplog.records)

    def test_disabled_log_buffers_nothing_but_bridges_warnings(self, caplog):
        log = NULL_TELEMETRY.events
        with caplog.at_level(logging.WARNING, logger="repro.telemetry"):
            log.emit("health.quarantined", level="warning",
                     message="device gpu0 quarantined", device="gpu0")
            log.emit("plan_cache.invalidated")      # info: not bridged
        assert len(log) == 0
        msgs = [r.message for r in caplog.records]
        assert any("gpu0 quarantined" in m for m in msgs)
        assert not any("plan_cache" in m for m in msgs)


# ---------------------------------------------------------------------------
# No-op fast path
# ---------------------------------------------------------------------------

class TestNoOpCost:
    def test_null_span_is_shared_singleton(self):
        t = NULL_TELEMETRY.tracer
        assert t.span("a", x=1) is t.span("b")      # no allocation
        assert NULL_TELEMETRY.metrics.counter("c") is \
            NULL_TELEMETRY.metrics.gauge("g")

    def test_noop_span_microbench(self):
        # acceptance: disabled telemetry must show no measurable overhead.
        # The shared no-op span costs ~0.3µs/span on this container; the
        # bound is loose for noisy CI but still orders of magnitude under
        # a real span.
        tracer = NULL_TELEMETRY.tracer
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with tracer.span("slot", device="gpu0/q0", units=128):
                pass
        per_span = (time.perf_counter() - t0) / n
        assert per_span < 5e-6, f"no-op span costs {per_span * 1e6:.2f}µs"

    def test_disabled_pipeline_records_nothing(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        sched.run(saxpy_tree(), saxpy_arrays())
        assert sched.telemetry is NULL_TELEMETRY
        assert sched.telemetry.tracer.events() == []
        assert sched.telemetry.metrics.snapshot() == {}


# ---------------------------------------------------------------------------
# Instrumented pipeline
# ---------------------------------------------------------------------------

class TestPipelineTracing:
    def test_run_trace_contains_span_model(self, tmp_path):
        tel = Telemetry()
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel)
        sched.run(saxpy_tree(), saxpy_arrays())
        trace = tel.export_trace(str(tmp_path / "trace.json"))
        assert validate_chrome_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"run", "plan", "dispatch", "attempt", "slot",
                "merge"} <= names

    def test_fault_injected_chain_trace(self, tmp_path):
        # acceptance: 2-SCT fault-injected run_chain yields a valid trace
        # with plan, per-slot compute, retry and merge spans
        tel = Telemetry()
        inj = FaultInjector(crash_on_call={"gpu0": [1]})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY, injector=inj),
                               telemetry=tel)
        with Session(sched) as s:
            runs = s.run_chain(chain_trees(), **saxpy_arrays()).get()
            path = tmp_path / "trace.json"
            s.export_trace(str(path))
        trace = json.loads(path.read_text())
        assert validate_chrome_trace(trace) == []
        names = {e["name"] for e in trace["traceEvents"]}
        assert {"run", "plan", "slot", "merge"} <= names
        retry_spans = [e for e in trace["traceEvents"]
                       if e["name"] == "attempt"
                       and e.get("args", {}).get("attempt", 0) >= 1]
        assert retry_spans, "retry attempt span missing"
        assert sum(r.stats.retries for r in runs) >= 1
        kinds = {e.kind for e in tel.events.records()}
        assert {"fault", "retry.repartition"} <= kinds

    def test_session_metrics_match_execution_stats(self):
        tel = Telemetry()
        inj = FaultInjector(crash_on_call={"gpu0": [2]})
        sched = make_scheduler(ThreadedExecutor(policy=POLICY, injector=inj),
                               telemetry=tel)
        stats = []
        with Session(sched) as s:
            for _ in range(3):
                stats.append(s.run(saxpy_tree(), **saxpy_arrays())
                             .get().stats)
            m = s.metrics()
        assert m["retries_total"] == sum(st.retries for st in stats)
        hits = m.get("plan_cache_hits_total", 0)
        misses = m.get("plan_cache_misses_total", 0)
        assert hits + misses == len(stats)
        assert hits / (hits + misses) == \
            pytest.approx(sched.plan_cache.hit_rate)
        assert m["merge_bytes_total"] == \
            sum(st.merge_bytes for st in stats)
        assert m["runs_total{status=ok}"] == \
            sum(1 for st in stats if st.ok)

    def test_device_busy_seconds_accounted(self):
        tel = Telemetry()
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel)
        sched.run(saxpy_tree(), saxpy_arrays())
        m = tel.metrics.snapshot()
        assert m.get("device_busy_seconds_total{device=gpu0}", 0) > 0
        assert m.get("device_busy_seconds_total{device=cpu0}", 0) > 0

    def test_plan_cache_invalidation_event(self):
        tel = Telemetry()
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel,
                               balancer=LoadBalancer(max_dev=1.5,
                                                     weight=1.0))
        sched.run(saxpy_tree(), saxpy_arrays())
        r = sched.run(saxpy_tree(), saxpy_arrays())   # forced "adjusted"
        assert r.action == "adjusted"
        evs = tel.events.records("plan_cache.invalidated")
        assert evs and evs[0].fields["reason"] == "share adjustment"
        assert tel.metrics.snapshot()[
            "plan_cache_invalidations_total"] >= 1

    def test_balancer_trigger_and_adjust_events(self):
        tel = Telemetry()
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel,
                               balancer=LoadBalancer(max_dev=1.5,
                                                     weight=1.0))
        sched.run(saxpy_tree(), saxpy_arrays())
        sched.run(saxpy_tree(), saxpy_arrays())
        kinds = [e.kind for e in tel.events.records()]
        assert "balancer.trigger" in kinds
        assert "balancer.adjust" in kinds
        adj = tel.events.records("balancer.adjust")[0]
        assert {"share_a_before", "share_a_after"} <= set(adj.fields)


# ---------------------------------------------------------------------------
# Counters satellite
# ---------------------------------------------------------------------------

class TestCounters:
    def test_scheduler_counters_namespaced(self):
        inj = FaultInjector(crash_on_call={"gpu0": [2]})
        ex = ThreadedExecutor(policy=POLICY, injector=inj)
        sched = make_scheduler(ex)
        for _ in range(3):
            sched.run(saxpy_tree(), saxpy_arrays())
        c = sched.counters()
        assert c["plan_cache.hits"] == sched.plan_cache.hits
        assert c["plan_cache.misses"] == sched.plan_cache.misses
        assert c["scheduler.runs"] == 3
        assert c["scheduler.retries"] == 1
        assert c["executor.pools_created"] == ex.pools_created
        assert c["executor.pool_reuses"] == ex.pool_reuses
        assert "balancer.balance_ops" in c
        assert "health.quarantined" in c

    def test_session_reexports_counters_and_resident_handoffs(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY))
        with Session(sched) as s:
            s.run_chain(chain_trees(), **saxpy_arrays()).get()
            c = s.counters()
        assert c["scheduler.resident_handoffs"] == 1    # first chain step
        assert c["scheduler.runs"] == 2


# ---------------------------------------------------------------------------
# Request ids, admission time and the profiler sink
# ---------------------------------------------------------------------------

#: every span one ``Session.submit`` request opens (``plan`` only when the
#: request is planned, ``merge`` only when its outputs are merged)
REQUEST_SPANS = {"submit", "node", "run", "plan", "dispatch", "attempt",
                 "slot", "compute", "writeback", "merge"}
#: child span -> parent span on the same thread
SAME_THREAD_PARENT = {"run": "node", "plan": "run", "dispatch": "run",
                      "attempt": "dispatch", "merge": "dispatch",
                      "compute": "slot", "writeback": "slot"}


def one_node_graph(sct=None):
    from repro.core import JobGraph
    graph = JobGraph()
    graph.add(sct or saxpy_tree())
    return graph


def chrome_intervals(events):
    """(name, tid, start, end, args) per span of a Chrome B/E stream; the
    args are the B event's and the notes on the E event together."""
    out, stacks = [], {}
    for e in events:
        if e["ph"] == "B":
            stacks.setdefault(e["tid"], []).append(e)
        elif e["ph"] == "E":
            b = stacks[e["tid"]].pop()
            out.append((b["name"], b["tid"], b["ts"], e["ts"],
                        {**b.get("args", {}), **e.get("args", {})}))
    return out


def queue_seconds(handle):
    """The admission wait that a one-node request's run carries."""
    (run,) = handle.runs.values()
    return run.stats.queue_seconds


def assert_nested(spans):
    """Each span in SAME_THREAD_PARENT lies inside a parent span of its
    own thread and request; a phase of a slot has the slot's class."""
    for name, line, s, e, attrs in spans:
        parent = SAME_THREAD_PARENT.get(name)
        if parent is None:
            continue
        assert any(n == parent and ln == line and ps <= s and e <= pe
                   and pa["request"] == attrs["request"]
                   and pa.get("cls") == attrs.get("cls")
                   for n, ln, ps, pe, pa in spans), (name, attrs)


class TestRequestSpans:
    def test_every_span_of_a_request_carries_its_id(self):
        tel = Telemetry()
        with Session(make_scheduler(ThreadedExecutor(policy=POLICY)),
                     telemetry=tel) as s:
            handles = [s.submit(one_node_graph(), **saxpy_arrays())
                       for _ in range(3)]
            for h in handles:
                h.result(30)
        spans = chrome_intervals(tel.tracer.events())
        assert {n for n, *_ in spans} == REQUEST_SPANS
        ids = {h.request_id for h in handles}
        assert {a["request"] for *_, a in spans} == ids
        assert {a["cls"] for n, *_, a in spans if n == "slot"} == {"a", "b"}
        assert_nested(spans)

    def test_pool_span_and_counters_are_gone_but_counted(self):
        tel = Telemetry()
        ex = ThreadedExecutor(policy=POLICY)
        with Session(make_scheduler(ex), telemetry=tel) as s:
            for _ in range(2):
                s.submit(one_node_graph(), **saxpy_arrays()).result(30)
            counters = s.counters()
        assert "pool" not in {e["name"] for e in tel.tracer.events()}
        assert not [k for k in tel.metrics.snapshot() if "pool" in k]
        assert counters["executor.pools_created"] == ex.pools_created >= 1
        assert counters["executor.pool_reuses"] == ex.pool_reuses >= 1

    def test_queued_s_is_the_wait_for_admission(self):
        release = threading.Event()

        def held(x):
            release.wait(30)
            return x + 1
        slow = kernel(held, name="held", inputs=[vector("x")],
                      outputs=[vector("z")])
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               max_inflight=1)
        first = sched.submit(one_node_graph(slow),
                             {"x": np.ones(64, np.float32)})
        second = sched.submit(one_node_graph(slow),
                              {"x": np.ones(64, np.float32)})
        time.sleep(0.2)
        release.set()
        first.result(30)
        second.result(30)
        sched.close()
        waited = [queue_seconds(h) for h in (first, second)]
        assert 0.0 <= waited[0] < 0.2 <= waited[1]
        # the queue is inside the node's span clock: the node starts
        # after its graph left the queue
        (start, _), = second.spans().values()
        assert start >= waited[1] * 1e6

    def test_a_fused_request_waits_its_fusion_window(self):
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               fusion_window=0.1)
        handles = [sched.submit(one_node_graph(), saxpy_arrays())
                   for _ in range(2)]
        for h in handles:
            h.result(30)
        sched.close()
        assert all(run.action == "fused"
                   for h in handles for run in h.runs.values())
        # the window opened when the first one joined; the second joined
        # later and waited less
        first, second = (queue_seconds(h) for h in handles)
        assert 0.09 <= first < 5.0 and 0.0 <= second <= first


@pytest.fixture(scope="module")
def profiled_requests(tmp_path_factory):
    """Three Session requests under ``Telemetry(profiler=True)`` inside a
    ``jax.profiler`` trace on the CPU: the program's spans as the trace
    reader sees them, (plane, line, name, start, end, metadata)."""
    import glob
    import os
    import jax
    from jax.profiler import ProfileData
    d = str(tmp_path_factory.mktemp("profile"))
    tel = Telemetry(profiler=True)
    accel = AcceleratorPlatform([DeviceInfo("accel0", "accel",
                                            jax_device=jax.devices()[0])])
    sched = Scheduler(host=HostPlatform.from_jax(), accel=accel,
                      executor=ThreadedExecutor(policy=POLICY),
                      kb=KnowledgeBase(), balancer=LoadBalancer(max_dev=0.0))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with Session(sched, telemetry=tel) as s:
            handles = [s.submit(one_node_graph(), **saxpy_arrays())
                       for _ in range(3)]
            for h in handles:
                h.result(60)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.append((plane.name, i, e.name[len("repro."):],
                                  e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return spans, {h.request_id for h in handles}, tel


class TestProfilerSink:
    def test_spans_land_on_the_host_plane_with_request_and_class(
            self, profiled_requests):
        spans, ids, tel = profiled_requests
        assert {p for p, *_ in spans} == {"/host:CPU"}
        assert {n for _, _, n, *_ in spans} == REQUEST_SPANS
        assert {a["request"] for *_, a in spans} == ids
        slots = [a for _, _, n, *_, a in spans if n == "slot"]
        assert {a["cls"] for a in slots} == {"a", "b"}
        # notes reach the profiler event too
        assert all("bound" in a and "placed" in a for a in slots)
        assert_nested([(n, ln, s, e, a) for _, ln, n, s, e, a in spans])
        # the profiler is the store: nothing went to the Chrome buffer
        assert tel.tracer.events() == []

    def test_profiler_spans_are_inert_outside_a_trace(self):
        tel = Telemetry(profiler=True)
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel)
        run = sched.run(saxpy_tree(), saxpy_arrays())
        sched.close()
        np.testing.assert_array_equal(run.outputs["z"],
                                      2.0 * np.arange(256) + 1)
        assert tel.tracer.events() == []
        assert tel.metrics.snapshot()["runs_total{status=ok}"] == 1

    def test_disabled_telemetry_ignores_the_profiler_flag(self):
        tel = Telemetry(enabled=False, profiler=True)
        assert tel.tracer.span("slot") is tel.tracer.span("merge")


# ---------------------------------------------------------------------------
# Quarantine logging satellite
# ---------------------------------------------------------------------------

class TestHealthLogging:
    def test_quarantine_warning_logged_without_telemetry(self, caplog):
        h = DeviceHealth(quarantine_after=2)
        with caplog.at_level(logging.WARNING, logger="repro.telemetry"):
            h.record_failure("gpu0")
            assert not caplog.records          # below threshold: silent
            h.record_failure("gpu0")
        msgs = [r.message for r in caplog.records]
        assert any("gpu0" in m and "2 consecutive failures" in m
                   for m in msgs)
        assert all(r.levelno == logging.WARNING for r in caplog.records)

    def test_reinstatement_warning_logged(self, caplog):
        h = DeviceHealth(quarantine_after=1)
        h.record_failure("gpu0")
        with caplog.at_level(logging.WARNING, logger="repro.telemetry"):
            h.record_success("gpu0")
        assert any("gpu0" in r.message and "reinstated" in r.message
                   for r in caplog.records)

    def test_quarantine_events_and_metrics_with_telemetry(self):
        tel = Telemetry()
        h = DeviceHealth(quarantine_after=1)
        h.telemetry = tel
        h.record_failure("gpu0")
        h.record_success("gpu0")
        kinds = [e.kind for e in tel.events.records()]
        assert kinds == ["health.quarantined", "health.reinstated"]
        m = tel.metrics.snapshot()
        assert m["quarantines_total"] == 1
        assert m["reinstatements_total"] == 1
        assert m["device_failures_total{device=gpu0}"] == 1


# ---------------------------------------------------------------------------
# Simulator determinism
# ---------------------------------------------------------------------------

class TestSimulatorTelemetry:
    def _run(self):
        tel = Telemetry(clock=counting_clock())
        inj = FaultInjector(crash_on_call={"gpu0": [1]})
        ex = SimulatedExecutor([SimDevice("gpu0", "gpu", flops=1e12),
                                SimDevice("cpu0", "cpu", flops=1e11,
                                          cores=4)],
                               seed=7, injector=inj)
        sched = make_scheduler(ex, telemetry=tel)
        for _ in range(3):
            sched.run(saxpy_tree(), saxpy_arrays())
        return tel

    def test_trace_is_deterministic(self):
        t1, t2 = self._run(), self._run()
        assert t1.tracer.chrome_trace()["traceEvents"] == \
            t2.tracer.chrome_trace()["traceEvents"]
        # overhead histograms time the host-side scheduler with the real
        # perf_counter even under the simulator; everything derived from
        # simulated stats.times must be bit-identical
        def sim_metrics(t):
            return {k: v for k, v in t.metrics.snapshot().items()
                    if not k.startswith("overhead_seconds")}
        assert sim_metrics(t1) == sim_metrics(t2)
        assert [e.kind for e in t1.events.records()] == \
            [e.kind for e in t2.events.records()]

    def test_simulated_slots_on_virtual_timeline(self):
        tel = self._run()
        trace = tel.tracer.chrome_trace()
        assert validate_chrome_trace(trace) == []
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert xs and all(e["name"] == "slot" for e in xs)
        # fault-injected slot annotated; the retry round starts on the
        # virtual clock only after the faulted round completes (all slots
        # in a round share ts = round start)
        assert any(e["args"].get("fault") == "crash" for e in xs)
        retry = [e for e in xs if e["args"]["attempt"] == 1]
        assert retry
        round0_ts = min(e["ts"] for e in xs)
        round0_end = round0_ts + max(e["dur"] for e in xs
                                     if e["ts"] == round0_ts)
        assert min(e["ts"] for e in retry) >= round0_end


# ---------------------------------------------------------------------------
# Overhead-breakdown invariants satellite
# ---------------------------------------------------------------------------

class TestOverheadInvariants:
    @pytest.mark.parametrize("plan_cache", [True, False])
    @pytest.mark.parametrize("persistent_pool", [True, False])
    def test_components_nonnegative_and_bounded(self, plan_cache,
                                                persistent_pool):
        sched = make_scheduler(
            ThreadedExecutor(policy=POLICY,
                             persistent_pool=persistent_pool),
            plan_cache=plan_cache)
        for _ in range(2):                      # cold + warm paths
            t0 = time.perf_counter()
            r = sched.run(saxpy_tree(), saxpy_arrays())
            wall = time.perf_counter() - t0
            s = r.stats
            components = (s.plan_seconds, s.pool_seconds,
                          s.dispatch_seconds, s.merge_seconds)
            assert all(c >= 0 for c in components)
            assert s.compute_seconds >= 0
            assert s.overhead_seconds == pytest.approx(sum(components))
            # components are disjoint sub-intervals of the scheduled run
            assert s.overhead_seconds + s.compute_seconds <= wall + 5e-3

    def test_stats_histogram_recorded(self):
        tel = Telemetry()
        sched = make_scheduler(ThreadedExecutor(policy=POLICY),
                               telemetry=tel)
        sched.run(saxpy_tree(), saxpy_arrays())
        snap = tel.metrics.snapshot()
        assert snap["overhead_seconds"]["count"] == 1
        assert snap["class_makespan_seconds{cls=a}"]["count"] == 1
        assert snap["class_makespan_seconds{cls=b}"]["count"] == 1


# ---------------------------------------------------------------------------
# Snapshot / embedding helpers
# ---------------------------------------------------------------------------

class TestExport:
    def test_metrics_block_schema(self):
        tel = Telemetry()
        tel.metrics.counter("runs_total").inc()
        block = metrics_block(tel)
        assert block["schema"] == "repro.metrics/v1"
        assert block["enabled"] is True
        assert block["metrics"] == {"runs_total": 1.0}
        json.dumps(block)                       # JSON-serialisable

    def test_telemetry_snapshot_serialisable(self):
        tel = Telemetry()
        tel.events.emit("fault", level="warning", device="gpu0")
        tel.metrics.histogram("lat").observe(0.1)
        json.dumps(tel.snapshot())

    def test_export_trace_writes_valid_json_file(self, tmp_path):
        tel = Telemetry()
        with tel.tracer.span("run"):
            pass
        path = tmp_path / "t.json"
        tel.export_trace(str(path))
        assert validate_chrome_trace(json.loads(path.read_text())) == []

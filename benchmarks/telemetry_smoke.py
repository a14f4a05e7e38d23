"""Telemetry smoke: fault-injected chain → validated Chrome trace.

CI gate for the observability subsystem.  Runs a 2-SCT ``run_chain``
with an injected gpu0 crash under a telemetry-enabled :class:`Session`,
then checks:

  * ``Session.export_trace`` writes a well-formed Chrome trace
    (``validate_chrome_trace``: required keys, matched B/E pairs);
  * the trace contains the submit, plan, per-slot compute and
    write-back, retry (attempt > 0) and merge spans the span model
    promises;
  * ``Session.metrics()`` retry / plan-cache counters match the
    ``ExecutionStats`` the same runs returned;
  * a fault event and a repartition event were logged;
  * the disabled-telemetry path stays cheap (microbench bound, loose
    enough for shared CI runners).

The exported ``trace.json`` is uploaded as a CI artifact — drop it on
https://ui.perfetto.dev or ``chrome://tracing`` to inspect a run.

Run:  PYTHONPATH=src python benchmarks/telemetry_smoke.py [--out trace.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import (AcceleratorPlatform, DeviceInfo, FaultInjector,
                        FaultPolicy, HostPlatform, KnowledgeBase,
                        LoadBalancer, NULL_TELEMETRY, Scheduler, Session,
                        Telemetry, ThreadedExecutor, kernel, scalar, vector,
                        validate_chrome_trace)

try:
    from benchmarks.report import embed_metrics
except ImportError:                     # run as `python benchmarks/...`
    from report import embed_metrics

POLICY = FaultPolicy(watchdog_multiple=1e6)

# required by the span model (docs/observability.md); "attempt" spans with
# attempt >= 1 are the retry spans
REQUIRED_SPANS = {"submit", "run", "plan", "dispatch", "attempt", "slot",
                  "compute", "writeback", "merge"}


def chain_kernels():
    k1 = kernel(lambda a, x, y: a * x + y, name="saxpy",
                inputs=[scalar("a"), vector("x"), vector("y")],
                outputs=[vector("z")])
    k2 = kernel(lambda a, z: z * a, name="scale",
                inputs=[scalar("a"), vector("z")], outputs=[vector("w")])
    return [k1, k2]


def make_session(telemetry: Telemetry) -> Session:
    host = HostPlatform(DeviceInfo("cpu0", "cpu", compute_units=4),
                        topology={"L2": 2, "NO_FISSION": 1})
    accel = AcceleratorPlatform([DeviceInfo("gpu0", "gpu")], max_overlap=2)
    inj = FaultInjector(crash_on_call={"gpu0": [1]})
    ex = ThreadedExecutor(policy=POLICY, injector=inj)
    sched = Scheduler(host=host, accel=accel, executor=ex,
                      kb=KnowledgeBase(), balancer=LoadBalancer(max_dev=0.0))
    return Session(sched, telemetry=telemetry)


def noop_span_cost(iters: int = 50_000) -> float:
    """Seconds per disabled-telemetry span (shared no-op singleton)."""
    tracer = NULL_TELEMETRY.tracer
    t0 = time.perf_counter()
    for _ in range(iters):
        with tracer.span("x", device="gpu0"):
            pass
    return (time.perf_counter() - t0) / iters


def smoke(out: str) -> dict:
    failures = []
    telemetry = Telemetry()
    n = 1 << 14
    arrays = {"a": np.float32(2.0),
              "x": np.arange(n, dtype=np.float32),
              "y": np.ones(n, dtype=np.float32)}

    with make_session(telemetry) as session:
        runs = session.run_chain(chain_kernels(), **arrays).get()
        trace = session.export_trace(out)
        metrics = session.metrics()
        counters = session.counters()

    # -- trace well-formedness + span model ----------------------------------
    errors = validate_chrome_trace(trace)
    if errors:
        failures.append(f"trace validation: {errors[:5]}")
    names = {e["name"] for e in trace["traceEvents"]}
    missing = REQUIRED_SPANS - names
    if missing:
        failures.append(f"missing spans: {sorted(missing)}")
    retry_spans = [e for e in trace["traceEvents"]
                   if e["name"] == "attempt"
                   and e.get("args", {}).get("attempt", 0) >= 1]
    if not retry_spans:
        failures.append("no retry (attempt >= 1) span in the trace")

    # -- metrics vs ExecutionStats -------------------------------------------
    stats_retries = sum(r.stats.retries for r in runs)
    if stats_retries < 1:
        failures.append("fault injection did not exercise the retry path")
    if metrics.get("retries_total", 0) != stats_retries:
        failures.append(
            f"retries_total={metrics.get('retries_total')} != "
            f"sum(stats.retries)={stats_retries}")
    hits = metrics.get("plan_cache_hits_total", 0)
    misses = metrics.get("plan_cache_misses_total", 0)
    hit_ratio = hits / (hits + misses) if hits + misses else 0.0
    if abs(hit_ratio - counters["plan_cache.hit_rate"]) > 1e-9:
        failures.append(
            f"metrics hit ratio {hit_ratio} != plan-cache counter "
            f"{counters['plan_cache.hit_rate']}")

    # -- event stream --------------------------------------------------------
    kinds = {e.kind for e in telemetry.events.records()}
    for needed in ("fault", "retry.repartition"):
        if needed not in kinds:
            failures.append(f"missing event kind {needed!r}")

    # -- disabled-telemetry cost ---------------------------------------------
    cost = noop_span_cost()
    if cost > 20e-6:            # loose CI bound; tests enforce a tighter one
        failures.append(f"no-op span cost {cost * 1e6:.2f}µs > 20µs")

    result = {
        "bench": "telemetry_smoke",
        "trace_events": len(trace["traceEvents"]),
        "span_names": sorted(names),
        "retry_spans": len(retry_spans),
        "event_kinds": sorted(kinds),
        "stats_retries": stats_retries,
        "noop_span_cost_us": cost * 1e6,
        "failures": failures,
    }
    return embed_metrics(result, telemetry)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default="trace.json",
                    help="Chrome trace output path")
    ap.add_argument("--json", default="BENCH_telemetry.json",
                    help="smoke-result JSON output path")
    args = ap.parse_args()

    result = smoke(args.out)
    with open(args.json, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps({k: v for k, v in result.items() if k != "metrics"},
                     indent=2))
    print(f"wrote {args.out} and {args.json}")
    for f in result["failures"]:
        print(f"SMOKE FAILED: {f}")
    raise SystemExit(1 if result["failures"] else 0)


if __name__ == "__main__":
    main()

"""Chip benchmark of the scheduler's main path, one cell per run.

    python3 bench/run.py --workload filter.4096.closed --seed 7 \\
        --seconds 30 --trace 0

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``: the SCT, its precision and
the chips of its accelerator class) and a traffic mix
(``bench/traffic/<mix>.json``, read by ``bench/load.py``).  Every request
of the window is a one-node ``JobGraph`` sent through ``Session.submit``:
``Scheduler`` (decide and plan, knowledge base, plan caches) ->
``ThreadedExecutor`` (accelerator slots on their chips, the host slot on
JAX's CPU device) -> merge.

Set-up makes the input pool from the seed, builds the Session, and
submits warm-up requests until the scheduler's split stops changing, so
the window starts from the settled split with every shape compiled.  Then
the window runs for ``--seconds``.  Afterwards the sampled requests'
outputs are compared with the float32 references of ``bench/reference.py``.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read by the
readers in ``bench/metrics/<metric>.py`` from the window's requests and
its ``jax.profiler`` trace.  Without a TPU, or with fewer chips than the
cell needs, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
#: actions of a run that changed the split (new partition shapes follow)
MOVED = ("adjusted", "built")

for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import load, reference  # noqa: E402


class BenchError(RuntimeError):
    """The run cannot be measured as asked."""


# ---------------------------------------------------------------------------
# What a cell is, from BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_spec(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_files(spec: Dict[str, Any], workload: str, root: str = ROOT
               ) -> Tuple[Dict, Dict, Dict]:
    """(cell, configuration, traffic mix) of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return cell, config, traffic


def cell_metrics(spec: Dict[str, Any], workload: str, kind: str
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if workload in m.get("workloads", [workload])]


def load_peaks(kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(peaks)})")
    return peaks[kind]


def reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

def build_session(accel, *, reuse_buffers: bool):
    """A Session as a user builds one: the accelerator class ``accel``,
    the host class on JAX's CPU device, Scheduler defaults except the
    online load balancer, whose trigger is set out of lbt's reach, so that
    the split stays the decided one (the configurations' ``scheduler``
    says why)."""
    from repro.core import (HostPlatform, KnowledgeBase, LoadBalancer,
                            Scheduler, Session, ThreadedExecutor)
    sched = Scheduler(host=HostPlatform.from_jax(), accel=accel,
                      executor=ThreadedExecutor(reuse_buffers=reuse_buffers),
                      kb=KnowledgeBase(),
                      balancer=LoadBalancer(trigger=float("inf")))
    return Session(sched)


def build_sct(config: Dict[str, Any], size: int):
    """The program's own SCT for the configuration (never a copy)."""
    from benchmarks.paper_suite import BENCHMARKS
    return BENCHMARKS[config["sct"]][0](size)


def make_pool(config: Dict[str, Any], traffic: Dict[str, Any], seed: int
              ) -> List[Dict[str, np.ndarray]]:
    return [reference.make_inputs(config["sct"], traffic["size"],
                                  load.input_seed(seed, i))
            for i in range(traffic["pool"])]


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    armed (JAX's backend-compile event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.armed and event == self.EVENT:
            with self._lock:
                self.count += 1


def settle(client: load.Client, traffic: Dict[str, Any]) -> Dict[str, Any]:
    """Sequential warm-up requests until ``quiet`` in a row leave the split
    unchanged (or ``cap_s`` passes); returns what it took."""
    quiet_needed = int(traffic["settle"]["quiet"])
    t_end = time.perf_counter() + float(traffic["settle"]["cap_s"])
    n = moved = quiet = 0
    while quiet < quiet_needed and time.perf_counter() < t_end:
        req = load.Request(n, n % len(client.pool), time.perf_counter())
        client.submit(req)
        if not req.settled.wait(load.LATE_S):
            raise BenchError("warm-up request did not finish")
        if req.error:
            raise BenchError(f"warm-up request failed: {req.error}")
        n += 1
        if req.action in MOVED:
            moved += 1
            quiet = 0
        else:
            quiet += 1
    return {"requests": n, "moved": moved, "settled": quiet >= quiet_needed}


def shutdown(session, timeout: float = 30.0) -> bool:
    """Shut the Session down, waiting at most ``timeout`` seconds; False
    when it did not finish (a slot thread the executor abandoned can hold
    it forever, and the run must still end)."""
    t = threading.Thread(target=session.shutdown, daemon=True,
                         name="bench-shutdown")
    t.start()
    t.join(timeout)
    return not t.is_alive()


def warm_up(client: load.Client, traffic: Dict[str, Any], seed: int
            ) -> Dict[str, Any]:
    """Settle the split; an open mix then runs a short burst at its rate,
    so that the window finds the scheduler's threads, pools and concurrent
    buffers in place."""
    info = {"settle": settle(client, traffic)}
    burst = float(traffic["settle"].get("burst_s", 0.0))
    if traffic["loop"] == "open" and burst > 0:
        due = load.open_schedule(traffic["rate"], burst, seed + 1)
        reqs = load.open_loop(client, due)["requests"]
        info["burst"] = {"requests": len(reqs),
                         "failed": sum(1 for r in reqs if not r.ok)}
    return info


# ---------------------------------------------------------------------------
# One measured run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What the metric readers read."""

    requests: List[load.Request]
    seconds: float
    window_end: float
    setup_s: float
    sct: str
    size: int
    peaks: Dict[str, Any]
    trace: Any = None                    # devtrace.Trace of a traced run
    planes: List[str] = dataclasses.field(default_factory=list)


def percentile(values: List[float], q: float) -> float:
    """Linear-interpolated percentile of every value (none dropped)."""
    return float(np.percentile(np.asarray(values, float), q))


def compare(config: Dict[str, Any], pool: List[Dict[str, np.ndarray]],
            reqs: List[load.Request], client: load.Client
            ) -> Dict[str, Dict[str, float]]:
    """Every kept output of the sampled requests against the reference of
    its input; returns the numbers compared, each beside its limit."""
    sampled = [r for r in reqs if client.sampled(r.index)]
    kept = [r for r in sampled if r.outputs is not None]
    worst = 0.0
    for p in sorted({r.pool for r in kept}):
        want = reference.reference(config["sct"], pool[p])
        for r in kept:
            if r.pool == p:
                worst = max(worst, reference.checked_error(r.outputs, want))
    return {"max_err": {"value": worst, "limit": config["max_err_limit"]},
            "missing": {"value": len(sampled) - len(kept), "limit": 0},
            "compared": {"value": len(kept), "min": 1}}


def checks_pass(checks: Dict[str, Dict[str, float]]) -> bool:
    return all((c["value"] >= c["min"]) if "min" in c
               else (c["value"] <= c["limit"]) for c in checks.values())


def prepare(config: Dict[str, Any], traffic: Dict[str, Any], seed: int,
            accel) -> load.Client:
    """Set-up up to the warm-up: JAX's compile cache, the SCT, the input
    pool and the Session, wrapped in the client that drives it."""
    import jax
    from repro.jaxcache import use_compile_cache
    use_compile_cache()
    # every program, however quick to compile, is kept: a later run's
    # set-up then loads all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    sct = build_sct(config, traffic["size"])
    pool = make_pool(config, traffic, seed)
    # concurrent requests may not share reusable output buffers: a caller
    # that keeps outputs while other runs go on must own them (the
    # executor documents that its outputs alias reused buffers)
    concurrent = traffic["loop"] == "open" or traffic.get("clients", 1) > 1
    session = build_session(accel, reuse_buffers=not concurrent)
    every = int(traffic["sample_every"])
    return load.Client(session, sct, pool, sample_every=every,
                       offset=load.sample_offset(seed, every),
                       copy_outputs=not concurrent)


def window(client: load.Client, traffic: Dict[str, Any], seconds: float,
           seed: int) -> Tuple[List[load.Request], Dict[str, float]]:
    """The measured window: the mix's loop for ``seconds``; returns its
    requests and, for an open loop, how late the generator ran."""
    import jax
    client.keep = True
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            if traffic["loop"] == "closed":
                return load.closed_loop(client, int(traffic["clients"]),
                                        seconds), {}
            got = load.open_loop(client, load.open_schedule(
                traffic["rate"], seconds, seed))
            return got.pop("requests"), got
    finally:
        client.keep = False


def measure(workload: str, seed: int, seconds: float, trace: bool, *,
            accel, devices, spec: Optional[Dict[str, Any]] = None,
            traffic: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell and return its result line.  ``accel`` is the
    accelerator platform, ``devices`` its ``jax.Device``s; ``traffic``
    replaces the cell's mix where given."""
    import jax
    spec = spec or load_spec()
    _, config, mix_file = cell_files(spec, workload)
    traffic = traffic or mix_file
    kind = devices[0].device_kind
    peaks = load_peaks(kind) if devices[0].platform == "tpu" else {}
    client = prepare(config, traffic, seed, accel)
    compiles = CompileCounter()
    trace_path = None
    try:
        warm = warm_up(client, traffic, seed)
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        compiles.armed = True
        t_window = time.perf_counter()
        reqs, late = window(client, traffic, seconds, seed)
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
            from bench import devtrace
            trace_path = devtrace.find_xplane(TRACE_DIR)
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices)
    finally:
        closed = shutdown(client.session)
    setup_s = t_window - T_PROCESS
    pool = client.pool

    ctx = Context(requests=reqs, seconds=seconds,
                  window_end=t_window + seconds, setup_s=setup_s,
                  sct=config["sct"], size=traffic["size"], peaks=peaks)
    dev = devices[0]
    device: Dict[str, Any] = {"platform": dev.platform, "kind": kind,
                              "count": len(devices),
                              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {}
    if trace:
        from bench import devtrace
        tr = devtrace.Trace(trace_path)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx.trace = tr
        ctx.planes = [f"{devtrace.DEVICE_PLANE}{d.id}" for d in devices]
        busy = tr.busy_s()
        device["busy_s"] = float(np.mean([busy.get(p, 0.0)
                                          for p in ctx.planes]))
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.device_ops(),
                               "idle_gaps": tr.idle_gaps()}
    kinds = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, workload, kinds):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    failed = sum(1 for r in reqs if not r.ok)
    checks = compare(config, pool, reqs, client)
    checks["failed"] = {"value": failed, "limit": 0}
    print(json.dumps({"info": dict(
        workload=workload, seed=seed, setup_s=setup_s, warm_up=warm,
        compiles_in_window=compiles.count, generator_late=late,
        retried=sum(1 for r in reqs if r.stats is not None
                    and r.stats.failures),
        session_closed=closed,
        **summary(reqs, ctx.window_end),
        compared=checks["compared"]["value"])}), flush=True)
    return {"correct": checks_pass(checks),
            "attempted": len(reqs), "failed": failed, "metrics": metrics,
            "device": device, **result, "checks": checks}


def summary(reqs: List[load.Request], window_end: float) -> Dict[str, Any]:
    """Counts of a window that are not metrics: requests, completions
    inside it, split changes, and the accelerator shares in effect."""
    return {"requests": len(reqs),
            "completed_in_window": sum(1 for r in reqs
                                       if r.ok and r.done <= window_end),
            "adjusted_in_window": sum(1 for r in reqs if r.action in MOVED),
            "accel_share": sorted({round(sum(r.accel_units.values())
                                         / r.units, 6)
                                   for r in reqs if r.ok and r.units})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = load_spec()
    cell, config, _ = cell_files(spec, args.workload)
    import jax
    if jax.default_backend() != "tpu":
        print(f"bench: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.core import AcceleratorPlatform
    devices = devices[:config["accel_chips"]]
    result = measure(args.workload, args.seed, args.seconds,
                     bool(args.trace), spec=spec,
                     accel=AcceleratorPlatform.from_jax(devices),
                     devices=devices)
    for name, c in result["checks"].items():
        bound = f"min {c['min']}" if "min" in c else f"limit {c['limit']}"
        print(f"check {name} {c['value']!r} {bound}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def exit_now(code: int) -> None:
    """End the process without joining the program's worker threads: one
    that the executor abandoned after a watchdog timeout never returns."""
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)


if __name__ == "__main__":
    try:
        code = main()
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        code = 2
    except Exception:
        import traceback
        traceback.print_exc()
        code = 1
    exit_now(code)

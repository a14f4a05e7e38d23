"""Run the scheduler's main path once on a TPU chip and check every result.

    python chip_smoke.py              # one chip: 5 paper SCTs + 4 kernels
    python chip_smoke.py --chips 4    # four chips: saxpy and filter pipeline
                                      # over all chips vs. over chip 0 alone

Each paper SCT is submitted through ``Session.submit`` (``Session`` ->
``Scheduler`` -> ``ThreadedExecutor``), with the accelerator class built
from the real TPU devices and the host class from JAX's CPU device, three
requests each, and every output is compared with a plain float32 NumPy
reference on the whole input.  One Session serves all five SCTs, one
after another, as it would serve a user.  Then the four paper Pallas
kernels run compiled (not interpreted) and are compared with
``repro.kernels.ref``.

The run fails on any slot fault, retry, quarantined device, zero
accelerator share, or a slot whose outputs live on a device other than
its own.  Without a TPU it exits non-zero before doing anything.  Each
phase prints one line; the last line is a JSON object naming the device.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from typing import Any, Dict, List, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))

#: paper problem classes (benchmarks/paper_suite.py BENCHMARKS), one each
SIZES = {"filter_pipeline": 4096, "fft": 256, "nbody": 8192,
         "saxpy": 10 ** 7, "segmentation": 64}
KERNEL_SIZES = {"saxpy": 10 ** 7, "filter_pipeline": 4096,
                "segmentation": 64, "nbody": 8192}
KERNEL_TOLERANCE = {"saxpy": 1e-6, "filter_pipeline": 1e-6,
                    "segmentation": 1e-6, "nbody": 1e-4}
FOUR_CHIP_SCTS = ("saxpy", "filter_pipeline")
REQUESTS = 3
RESULT_TIMEOUT = 600.0


class SmokeFailure(RuntimeError):
    pass


def _import_repo():
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def build_session(accel):
    """Session over a Scheduler whose accelerator class is the
    ``AcceleratorPlatform`` ``accel`` and whose host class is JAX's CPU
    device."""
    from repro.core import (HostPlatform, KnowledgeBase, Scheduler, Session,
                            Telemetry, ThreadedExecutor)
    telemetry = Telemetry()
    sched = Scheduler(host=HostPlatform.from_jax(), accel=accel,
                      executor=ThreadedExecutor(), kb=KnowledgeBase(),
                      telemetry=telemetry)
    return Session(sched), telemetry


def slot_devices(telemetry) -> List[Tuple[str, str, Tuple[str, ...]]]:
    """(slot, bound device, devices holding its outputs) per finished
    slot span, from the executor's trace."""
    stacks: Dict[int, list] = {}
    out = []
    for ev in telemetry.tracer.events():
        if ev["ph"] == "B":
            stacks.setdefault(ev["tid"], []).append(ev)
        elif ev["ph"] == "E" and stacks.get(ev["tid"]):
            begin = stacks[ev["tid"]].pop()
            late = ev.get("args", {})
            if ev["name"] == "slot" and "placed" in late:
                out.append((begin["args"]["device"], late["bound"],
                            tuple(late["placed"])))
    return out


def check_placement(session, telemetry) -> Dict[str, str]:
    """Every slot resolved to its own device and kept its outputs there;
    returns the slot -> device map."""
    sched = session.scheduler
    infos = {d.name: d for d in sched.accel.devices}
    infos[sched.host.device.name] = sched.host.device
    seen: Dict[str, str] = {}
    for slot, bound, placed in slot_devices(telemetry):
        info = infos[slot.split("/")[0]]
        if info.jax_device is None or bound != str(info.jax_device):
            raise SmokeFailure(f"slot {slot} bound to {bound}, "
                               f"expected {info.jax_device}")
        if placed != (bound,):
            raise SmokeFailure(f"slot {slot} on {bound} left outputs on "
                               f"{list(placed)}")
        seen[slot] = bound
    if not seen:
        raise SmokeFailure("no slot spans recorded")
    return seen


def sct_phase(session, telemetry, name: str, size: int, *,
              requests: int = REQUESTS, seed: int = 0,
              keep_outputs: bool = False) -> Dict[str, Any]:
    """Submit ``requests`` requests of one paper SCT one after another,
    compare each with the NumPy reference, and check the run's health.
    ``keep_outputs`` returns a copy of the last request's outputs."""
    from benchmarks.paper_suite import (BENCHMARKS, TOLERANCE, make_inputs,
                                        max_error, reference)
    from repro.core import JobGraph
    sct = BENCHMARKS[name][0](size)
    inputs = make_inputs(name, size, seed)
    want = reference(name, inputs)
    telemetry.tracer.clear()
    errors, shares, actions = [], [], []
    t0 = time.perf_counter()
    first = 0.0
    for i in range(requests):
        graph = JobGraph()
        node = graph.add(sct)
        result = session.submit(graph, **inputs).result(RESULT_TIMEOUT)
        if i == 0:
            first = time.perf_counter() - t0
        run = result.runs[node]
        if run.stats.failures or run.stats.retries:
            raise SmokeFailure(
                f"{name} request {i}: {len(run.stats.failures)} failures, "
                f"{run.stats.retries} retries: "
                f"{[str(f) for f in run.stats.failures]}")
        part = run.node_plan.part
        accel_units = sum(u for s, u in zip(part.slots, part.units)
                          if s.device_type != "cpu")
        shares.append(accel_units / max(sum(part.units), 1))
        actions.append(run.action)
        # compare now: the executor reuses its output buffers next run
        errors.append(max_error(result.outputs, want))
        kept = {k: v.copy() for k, v in result.outputs.items()} \
            if keep_outputs and i == requests - 1 else None
    wall = time.perf_counter() - t0
    quarantined = session.scheduler.health.quarantined()
    if quarantined:
        raise SmokeFailure(f"{name}: quarantined devices {quarantined}")
    if min(shares) <= 0:
        raise SmokeFailure(f"{name}: accelerator share 0 ({shares})")
    if max(errors) > TOLERANCE[name]:
        raise SmokeFailure(f"{name}: error {max(errors)} > tolerance "
                           f"{TOLERANCE[name]}")
    rec = {"phase": f"sct:{name}", "size": size, "requests": requests,
           "wall_s": wall, "first_request_s": first,
           "max_err": max(errors), "tol": TOLERANCE[name],
           "accel_share": shares, "actions": actions,
           "slots": check_placement(session, telemetry)}
    if kept is not None:
        rec["outputs"] = kept
    return rec


def kernel_inputs(name: str, size: int, seed: int = 0):
    import numpy as np
    from benchmarks.paper_suite import SEG_PLANE
    rng = np.random.default_rng(seed)
    f32 = np.float32
    if name == "saxpy":
        return (f32(2.5), rng.standard_normal(size, f32),
                rng.standard_normal(size, f32))
    if name == "filter_pipeline":
        return (rng.random((size, size), f32) * f32(255),)
    if name == "segmentation":
        return (rng.random((size, *SEG_PLANE), f32) * f32(255),)
    if name == "nbody":
        return (rng.standard_normal((size, 3), f32),
                rng.random(size, f32) + f32(0.1))
    raise KeyError(name)


def kernel_phase(name: str, size: int, device, *, interpret: bool = False,
                 seed: int = 0) -> Dict[str, Any]:
    """One paper Pallas kernel through ``repro.kernels.ops`` on ``device``
    against its ``repro.kernels.ref`` oracle, computed on the CPU."""
    import jax
    import numpy as np
    from repro.kernels import ops, ref
    fns = {"saxpy": (ops.saxpy, ref.saxpy_ref),
           "filter_pipeline": (ops.filter_pipeline, ref.filter_pipeline_ref),
           "segmentation": (ops.segmentation, ref.segmentation_ref),
           "nbody": (ops.nbody_accelerations, ref.nbody_ref)}
    kern, oracle = fns[name]
    host_args = kernel_inputs(name, size, seed)
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(jax.jit(oracle)(*host_args))
    args = jax.device_put(host_args, device)
    fn = jax.jit(functools.partial(kern, interpret=interpret))
    t0 = time.perf_counter()
    got = fn(*args).block_until_ready()
    first = time.perf_counter() - t0
    t1 = time.perf_counter()
    got = fn(*args).block_until_ready()
    again = time.perf_counter() - t1
    placed = sorted(str(d) for d in got.devices())
    if placed != [str(device)]:
        raise SmokeFailure(f"kernel {name} output on {placed}, "
                           f"expected {device}")
    scale = max(float(np.abs(want).max()), 1.0)
    err = float(np.abs(np.asarray(got) - want).max()) / scale
    if err > KERNEL_TOLERANCE[name]:
        raise SmokeFailure(f"kernel {name}: error {err} > tolerance "
                           f"{KERNEL_TOLERANCE[name]}")
    return {"phase": f"kernel:{name}", "size": size,
            "wall_s": first + again, "first_request_s": first,
            "second_call_s": again, "max_err": err,
            "tol": KERNEL_TOLERANCE[name], "device": placed[0]}


def four_chip_phase(name: str, size: int, accel, *,
                    requests: int = REQUESTS, seed: int = 0
                    ) -> Dict[str, Any]:
    """The same requests with the accelerator class ``accel`` over all its
    devices and over its first device alone: both legs must match the
    reference and each other, and the wide leg must use every device."""
    from benchmarks.paper_suite import TOLERANCE, max_error
    from repro.core import AcceleratorPlatform
    devices = accel.devices
    legs = {}
    for label, members in (("all", devices), ("chip0", devices[:1])):
        session, telemetry = build_session(AcceleratorPlatform(members))
        with session:
            legs[label] = sct_phase(session, telemetry, name, size,
                                    requests=requests, seed=seed,
                                    keep_outputs=True)
    accel = {d for slot, d in legs["all"]["slots"].items()
             if not slot.startswith("host/")}
    if len(accel) != len(devices):
        raise SmokeFailure(f"{name}: wide run used {sorted(accel)}, "
                           f"expected {len(devices)} devices")
    cross = max_error(legs["all"].pop("outputs"),
                      legs["chip0"].pop("outputs"))
    if cross > TOLERANCE[name]:
        raise SmokeFailure(f"{name}: {len(devices)}-chip and 1-chip outputs "
                           f"differ by {cross}")
    return {"phase": f"chips{len(devices)}:{name}", "size": size,
            "cross_err": cross, "accel_devices": sorted(accel),
            "wide": legs["all"], "chip0": legs["chip0"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {jax.default_backend()!r}",
              file=sys.stderr)
        return 2
    _import_repo()
    from repro.core import AcceleratorPlatform
    from repro.jaxcache import use_compile_cache
    use_compile_cache()

    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    try:
        if args.chips == 4:
            for name in FOUR_CHIP_SCTS:
                accel = AcceleratorPlatform.from_jax(devices[:4])
                print(json.dumps(four_chip_phase(name, SIZES[name], accel)),
                      flush=True)
        else:
            session, telemetry = build_session(
                AcceleratorPlatform.from_jax(devices[:1]))
            with session:
                for name, size in SIZES.items():
                    print(json.dumps(sct_phase(session, telemetry, name,
                                               size)), flush=True)
            for name, size in KERNEL_SIZES.items():
                print(json.dumps(kernel_phase(name, size, devices[0])),
                      flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

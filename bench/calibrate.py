"""Readings behind a cell's correctness limit, on the chip, in one process.

    python3 bench/calibrate.py --workload filter.4096.closed \\
        --seeds 101,102,...,112 --control-seeds 201,202,203 --seconds 8

The program's reading: for each seed, the cell's own input pool, traffic
and load, a short window through the same Session, and the same
comparison that decides ``correct`` (``bench/run.py:compare``), with more
of the window's requests kept than a run keeps, so that the short window
compares about as many requests as a full run does.  The largest of these is the limit's
lower reading.

The control's reading: for each control seed, the reference computed in
bfloat16 on the chip (``bench/reference.py:control``) over the same pool,
compared with the float32 reference by the same ``max_error``.  The
smallest of these is the limit's upper reading.  One JSON line per
reading, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import reference, run  # noqa: E402


def program_reading(client, config, traffic, seed: int, seconds: float,
                    every: int):
    client.pool = run.make_pool(config, traffic, seed)
    client.every, client.offset = every, 0
    reqs, _ = run.window(client, traffic, seconds, seed)
    checks = run.compare(config, client.pool, reqs, client)
    return {"seed": seed, "requests": len(reqs),
            "failed": sum(1 for r in reqs if not r.ok),
            "compared": checks["compared"]["value"],
            "max_err": checks["max_err"]["value"]}


def control_reading(config, traffic, seed: int):
    pool = run.make_pool(config, traffic, seed)
    worst = min_err = None
    for inputs in pool:
        want = reference.reference(config["sct"], inputs)
        err = reference.checked_error(
            reference.control(config["sct"], inputs), want)
        worst = err if worst is None else max(worst, err)
        min_err = err if min_err is None else min(min_err, err)
    return {"seed": seed, "inputs": len(pool), "control_max_err": worst,
            "control_min_err": min_err}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = run.load_spec()
    import jax
    if jax.default_backend() != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from repro.core import AcceleratorPlatform
    _, config, traffic = run.cell_files(spec, args.workload)
    every = max(1, int(traffic["sample_every"] * args.seconds
                       / spec["run_seconds"]))
    devices = jax.devices()[:config["accel_chips"]]
    seeds = [int(s) for s in args.seeds.split(",")]
    client = run.prepare(config, traffic, seeds[0],
                         AcceleratorPlatform.from_jax(devices))
    program = []
    try:
        run.warm_up(client, traffic, seeds[0])
        for seed in seeds:
            program.append(program_reading(client, config, traffic, seed,
                                           args.seconds, every))
            print(json.dumps(program[-1]), flush=True)
    finally:
        run.shutdown(client.session)
    control = []
    with jax.default_device(devices[0]):
        for seed in (int(s) for s in args.control_seeds.split(",")):
            control.append(control_reading(config, traffic, seed))
            print(json.dumps(control[-1]), flush=True)
    print(json.dumps({
        "workload": args.workload,
        "lower": max(r["max_err"] for r in program),
        "upper": min(r["control_max_err"] for r in control),
        "limit": config["max_err_limit"],
        "failed": sum(r["failed"] for r in program),
        "compared": sum(r["compared"] for r in program)}), flush=True)
    return 0


if __name__ == "__main__":
    run.exit_now(main())

"""Find an open-loop cell's knee: the highest offered rate the system
sustains, by a sweep of rates on the chip, in one process.

    python3 bench/sweep.py --workload saxpy.1m.open --seed 5 \\
        --rates 50,100,200,400 --seconds 10

Set-up is the cell's own (input pool, Session, warm-up); then each rate
runs one open-loop window of ``--seconds`` and prints one JSON line:
offered and completed rates, latency median and 95th percentile over every
request, the median latency of the window's first and last quarter (a
backlog that grows shows as a last quarter slower than the first),
failures, split changes and the generator's lateness.  A rate is sustained
when nothing failed, at least 97 % of its requests completed inside the
window, and the last quarter's median is under twice the first's.  The
cell's rate is then fixed at about 0.8 of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import run  # noqa: E402


def one_rate(client, traffic, rate: float, seconds: float, seed: int):
    mix = dict(traffic, rate=rate)
    reqs, late = run.window(client, mix, seconds, seed)
    lat = [r.latency for r in reqs]
    q = max(1, len(reqs) // 4)
    first = run.percentile(lat[:q], 50)
    last = run.percentile(lat[-q:], 50)
    window_end = reqs[0].due + seconds
    done = sum(1 for r in reqs if r.ok and r.done <= window_end)
    failed = sum(1 for r in reqs if not r.ok)
    return {"rate": rate, "requests": len(reqs),
            "completed_rps": done / seconds,
            "p50_s": run.percentile(lat, 50), "p95_s": run.percentile(lat, 95),
            "first_quarter_p50_s": first, "last_quarter_p50_s": last,
            "failed": failed,
            "adjusted": sum(1 for r in reqs if r.action in run.MOVED),
            "late_max_s": late.get("late_max_s"),
            "sustained": (failed == 0 and done >= 0.97 * len(reqs)
                          and last < 2 * first)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    import jax
    if jax.default_backend() != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    from repro.core import AcceleratorPlatform
    _, config, traffic = run.cell_files(run.load_spec(), args.workload)
    devices = jax.devices()[:config["accel_chips"]]
    client = run.prepare(config, traffic, args.seed,
                         AcceleratorPlatform.from_jax(devices))
    try:
        print(json.dumps({"warm_up": run.warm_up(client, traffic,
                                                 args.seed)}), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            row = one_rate(client, traffic, rate, args.seconds,
                           args.seed + i)
            print(json.dumps(row), flush=True)
            if row["failed"] > 0.1 * row["requests"]:
                break           # far past the knee: stop loading the chip
            # let a backlog drain before the next rate
            run.settle(client, traffic)
    finally:
        run.shutdown(client.session)
    return 0


if __name__ == "__main__":
    run.exit_now(main())

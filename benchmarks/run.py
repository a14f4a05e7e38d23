"""Benchmark entry point: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

``--full`` runs the complete parameterisation classes (slower);
the default exercises every benchmark end-to-end at reduced size.
Prints a ``name,...`` CSV block at the end for machine consumption.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="run a single module (e.g. 'hybrid')")
    args = ap.parse_args()

    from repro.jaxcache import use_compile_cache
    use_compile_cache()
    from benchmarks import (fission, hybrid, kb_derivation,
                            load_fluctuation, maxdev, profile_construction,
                            roofline)
    modules = {
        "fission": fission,
        "profile_construction": profile_construction,
        "hybrid": hybrid,
        "maxdev": maxdev,
        "kb_derivation": kb_derivation,
        "load_fluctuation": load_fluctuation,
        "roofline": roofline,
    }
    if args.only:
        modules = {args.only: modules[args.only]}

    all_lines = []
    for name, mod in modules.items():
        t0 = time.time()
        try:
            lines = mod.main(full=args.full)
            all_lines.extend(lines or [])
            print(f"-- {name} done in {time.time() - t0:.1f}s --\n")
        except Exception as e:           # keep the harness going
            print(f"-- {name} FAILED: {e!r} --\n")
            all_lines.append(f"{name},FAILED,{e!r}")
            import traceback
            traceback.print_exc()
            return 1

    print("==== CSV summary ====")
    for line in all_lines:
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""A cell's whole run on whatever backend JAX has, at a size a CPU can hold:
the rehearsal before a chip run.  Skips only the look for a chip; its numbers
are not measurements and it prints them under no metric's authority.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py --workload <cell> \\
        --seed 1 --seconds 5 --trace 0 [--rows-per-region 2000] [--warmup 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--rows-per-region", type=int, default=2000)
    ap.add_argument("--warmup", type=float, default=3.0)
    own, rest = ap.parse_known_args(argv)
    args = run.parse(rest)
    run.place_cache()
    import jax

    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}
    if device["kind"] not in run.load_json(run.HERE, "peaks.json"):
        # a rehearsal has no peak to hold anything to
        run.Run.peaks = lambda self: {"hbm_bytes_per_s": float("inf")}
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    result = run.run_cell(args, device, bench, {
        "rehearsal": True,
        "config": {"rows_per_region": own.rows_per_region},
        "traffic": {"warmup_seconds": own.warmup, "trace_seconds": 2}})
    print(json.dumps(result, indent=1, default=str))
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)

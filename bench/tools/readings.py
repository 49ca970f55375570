#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers the comparison
holds each run to: the program's on many seeds (the lower readings), the
control's, the plain reference one precision step below the configuration
in the program's place (the upper readings), and those of planted faults.
One process, one JSON line per seed.

    python3 bench/tools/readings.py --workload fl_chip_share \\
        --seeds 101,102,103 --control 1 --faults half_batch
    python3 bench/tools/readings.py --workload serve_typing \\
        --seeds 101,102,103 --control 1 --seconds 5
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run as bench_run  # noqa: E402


def fl_seed(ctx, control: bool, faults):
    from bench.drivers import fl
    t0 = time.perf_counter()
    s = fl.setup(ctx)
    s.engine = s.state = None
    gc.collect()
    t1 = time.perf_counter()
    ref = fl.reference(ctx, s)
    t2 = time.perf_counter()
    out = {"program": fl.readings(s, fl.program_run(s), ref),
           "setup_s": t1 - t0, "reference_s": t2 - t1}
    if control:
        out["control"] = fl.readings(s, fl.reference(ctx, s, "fp8"), ref)
    for f in faults:
        out[f] = fl.readings(s, fl.reference(ctx, s, fault=f), ref)
    return out


def serve_seed(ctx, control: bool, faults):
    from bench.drivers import serve
    keep = {}
    res = serve.run(ctx, keep=keep)
    checks = res.checks.as_dict()
    out = {"program": {k: v["value"] for k, v in checks.items()},
           "correct": res.checks.correct, **res.end_to_end}
    if control:
        out["control"] = serve.served_gaps(
            ctx, keep["params"], keep["sessions"], keep["results"],
            precision="fp8")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    faults = [f for f in args.faults.split(",") if f]
    counter = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        _, _, ctx = bench_run.prepare(args.workload, seed, args.seconds,
                                      False, counter)
        counter = ctx.compiles
        kind = ctx.config["driver"]
        fn = fl_seed if kind == "fl" else serve_seed
        out = fn(ctx, bool(args.control), faults)
        print(json.dumps({"seed": seed, **out}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Sweep the open-loop arrival rate of a serving cell on the chip, in one
process, and print one JSON line per rate: sessions, how long the engine
took to drain after arrivals stopped, the latency tails and the admission
time. The knee is the highest rate at which the queue does not grow across
the window (the drain stays short).

    python3 bench/tools/knee.py --workload serve_typing --rates 50,100,200 \\
        --seconds 8 --seeds 11,12
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve_typing")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seeds", default="11")
    args = ap.parse_args(argv)
    counter = None
    runs = [(float(r), int(s)) for r in args.rates.split(",")
            for s in args.seeds.split(",")]
    for rate, seed in runs:
        _, _, ctx = bench_run.prepare(args.workload, seed, args.seconds,
                                      False, counter)
        counter = ctx.compiles
        ctx.mix["rate_per_s"] = rate
        from bench.harness import readers
        from bench.harness.spec import driver
        res = driver(ctx.config).run(ctx)
        rec = res.record
        print(json.dumps({
            "rate_per_s": rate, "seed": seed, "sessions": rec["sessions"],
            "drain_s": rec["drained_s"] - ctx.seconds,
            "failed": res.failed, **res.end_to_end,
            "admit_ms": readers.median_ms(rec["admission_s"]),
            "gen_late_p95_ms": readers.p95_ms(rec["late_s"]),
            "max_stall_ms": readers.max_ms(rec["stalls_s"]),
            "compiles_in_window": rec["compiles_in_window"],
            "correct": res.checks.correct}), flush=True)
        for note in res.notes:
            print(note, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

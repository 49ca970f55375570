#!/usr/bin/env python3
"""Look into the long engine steps of a serving cell: run the cell in one
process and, for every ``ServeEngine.step`` of the window, record its wall
time beside the CPU time the calling thread and the whole process spent in
it, and the scheduler's counters of the calling thread where the kernel
exposes them (``/proc/thread-self/schedstat``: on the CPU, waiting to run).
A heartbeat thread wakes every 2 ms; the longest time it overslept during a
step, and the Python frame the stepping thread stood in when it woke, tell
whether the whole process stood still or the stepping thread alone.

    python3 bench/tools/stalls.py --workload serve_typing --seeds 11,12 \\
        --rate 120 --seconds 15

One JSON line per step longer than ``--over-ms``, then one summary line per
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import run as bench_run  # noqa: E402

BEAT_S = 0.002


def schedstat():
    try:
        with open("/proc/thread-self/schedstat") as f:
            run_ns, wait_ns, slices = (int(x) for x in f.read().split()[:3])
        return run_ns, wait_ns, slices
    except (OSError, ValueError):
        return None


class Heartbeat(threading.Thread):
    """Wakes every ``BEAT_S``; keeps its longest oversleep since the last
    ``take`` and where the stepping thread stood then."""

    def __init__(self, main_id: int):
        super().__init__(daemon=True)
        self.main_id = main_id
        self.stop = threading.Event()
        self.worst, self.where = 0.0, ""

    def run(self):
        while not self.stop.is_set():
            t = time.perf_counter()
            time.sleep(BEAT_S)
            late = time.perf_counter() - t - BEAT_S
            if late > self.worst:
                frame = sys._current_frames().get(self.main_id)
                self.worst = late
                self.where = (f"{Path(frame.f_code.co_filename).name}:"
                              f"{frame.f_lineno} {frame.f_code.co_name}"
                              if frame else "")

    def take(self):
        out = (self.worst, self.where)
        self.worst, self.where = 0.0, ""
        return out


def watch(engine, steps: list, beat: Heartbeat):
    orig = engine.step

    def step():
        queued = engine.queued
        s0 = schedstat()
        beat.take()
        w0, t0, p0 = (time.perf_counter(), time.thread_time(),
                      time.process_time())
        out = orig()
        w1, t1, p1 = (time.perf_counter(), time.thread_time(),
                      time.process_time())
        s1 = schedstat()
        late, where = beat.take()
        row = {"at_s": w1, "wall_ms": 1e3 * (w1 - w0),
               "thread_cpu_ms": 1e3 * (t1 - t0),
               "process_cpu_ms": 1e3 * (p1 - p0), "admitted": queued,
               "heartbeat_late_ms": 1e3 * late, "stepper_at": where}
        if s0 and s1:
            row["runqueue_wait_ms"] = (s1[1] - s0[1]) / 1e6
            row["on_cpu_ms"] = (s1[0] - s0[0]) / 1e6
        steps.append(row)
        return out

    engine.step = step


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="serve_typing")
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="sessions per second; 0 keeps the mix's rate")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--over-ms", type=float, default=40.0)
    args = ap.parse_args(argv)
    counter = None
    for seed in [int(s) for s in args.seeds.split(",")]:
        _, _, ctx = bench_run.prepare(args.workload, seed, args.seconds,
                                      False, counter)
        counter = ctx.compiles
        if args.rate:
            ctx.mix["rate_per_s"] = args.rate
        from bench.harness.spec import driver
        steps: list = []
        beat = Heartbeat(threading.get_ident())
        beat.start()
        res = driver(ctx.config).run(ctx, alter=lambda e: watch(e, steps,
                                                                beat))
        beat.stop.set()
        beat.join()
        t0 = steps[0]["at_s"] if steps else 0.0
        long_steps = [dict(r, seed=seed, at_s=r["at_s"] - t0)
                      for r in steps if r["wall_ms"] > args.over_ms]
        for r in long_steps:
            print(json.dumps(r), flush=True)
        print(json.dumps({
            "seed": seed, "rate_per_s": ctx.mix["rate_per_s"],
            "steps": len(steps), "long_steps": len(long_steps),
            "schedstat": schedstat() is not None,
            "correct": res.checks.correct, **res.end_to_end}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The chip benchmark's one command:

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It reads ``BENCHMARK.json`` at the root of the checkout, finds the cell, its
configuration file and its traffic mix by name, refuses to run without the
accelerator the cell asks for, and hands the rest to the configuration's
driver (``bench/drivers/<driver>.py``). With ``--trace 0`` the result holds
the cell's end-to-end metrics; with ``--trace 1`` a profiled run's per-layer
metrics, each computed by its reader ``bench/metrics/<metric>.py``. The last
line of standard output is one JSON object; the numbers compared with the
plain reference end standard error and the result line, each beside its
limit.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"


def _paths() -> None:
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def _compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, handed to the program through the variable it reads."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def prepare(workload: str, seed: int, seconds: float, trace: bool,
            counter=None):
    """(BENCHMARK.json, the cell, the run's context): everything a driver
    needs, after the device check."""
    _paths()
    from bench.harness import spec
    bench = spec.load_benchmark(ROOT)
    cell = spec.cell(bench, workload)
    config, mix = spec.config_of(ROOT, bench, cell), spec.mix_of(ROOT, cell)
    _compile_cache()
    from bench.harness.device import require_accelerator
    devices = require_accelerator(int(cell["chips"]))
    from bench.harness.clock import CompileCounter
    from bench.harness.context import Context
    out = OUT_DIR / f"{workload}-{seed}-{int(trace)}"
    out.mkdir(parents=True, exist_ok=True)
    ctx = Context(config=config, mix=mix, seed=seed,
                  seconds=seconds, trace=trace, devices=devices,
                  out_dir=str(out), compiles=counter or CompileCounter(),
                  t_process=T_PROCESS)
    return bench, cell, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, ctx = prepare(args.workload, args.seed, args.seconds,
                               bool(args.trace))
    from bench.harness import spec
    result = spec.driver(ctx.config).run(ctx)
    line = spec.result_line(ROOT, bench, cell, ctx, result)
    for note in result.notes:
        print(note, file=sys.stderr)
    for check in result.checks.lines():
        print(check, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

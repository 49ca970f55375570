"""What a driver is handed, and what it hands back."""
from __future__ import annotations

import contextlib
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from bench.harness import trace as trace_lib
from bench.harness.compare import Checks

# host spans the harness writes around its own calls into the program
SPANS = ("sim.run", "engine.submit", "engine.step", "generator.wait",
         "bench.window")


@dataclass
class Context:
    config: Dict            # the configuration's file
    mix: Dict               # the traffic mix's file
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    out_dir: str            # scratch for this run, inside the checkout
    compiles: Any           # harness.clock.CompileCounter
    t_process: float        # perf_counter() at process start

    def setup_seconds(self) -> float:
        return time.perf_counter() - self.t_process

    @contextlib.contextmanager
    def traced(self, holder: Dict):
        """Profile the enclosed window when ``trace`` is on; the reduced
        trace lands in ``holder["trace"]``."""
        if not self.trace:
            yield
            return
        import jax
        tdir = os.path.join(self.out_dir, "trace")
        shutil.rmtree(tdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # Python calls: too costly
        options.host_tracer_level = 1       # keeps the harness's spans
        jax.profiler.start_trace(tdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
        holder["trace"] = trace_lib.load(tdir, SPANS)


@dataclass
class Result:
    end_to_end: Dict[str, float]
    record: Dict[str, Any]          # what the per-layer readers read
    checks: Checks
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Optional[Dict] = None
    notes: List[str] = field(default_factory=list)

"""Shared arithmetic of the per-layer metric readers in ``bench/metrics``.

Each reader takes the run's record (``drivers.*.run``) and returns one
number, or None where the run left nothing to read: a share of a roofline
or of a peak is never reported as 0 for want of events.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.harness import flops
from bench.harness import trace as trace_lib
from bench.harness.peaks import peaks_for

TICK_MODULE = r"_tick"


def device_idle_pct(record) -> Optional[float]:
    t = record.get("trace")
    if t is None or trace_lib.window_s(t) <= 0:
        return None
    return 100.0 * (1.0 - trace_lib.busy_s(t) / trace_lib.window_s(t))


def compiles(record) -> float:
    return float(record["compiles_in_window"])


def train_mfu_pct(record) -> Optional[float]:
    if not record.get("rounds"):
        return None
    peak = peaks_for(record["device_kind"])["bf16_flops"]
    per_token = flops.cifg_lm_train_flops_per_token(
        record["embed_dim"], record["hidden"], record["vocab"])
    done = record["trained_tokens"] * per_token
    return 100.0 * done / (record["window_s"] * record["chips"] * peak)


def tick(record):
    """(device seconds, count) of the decode tick programs traced."""
    t = record.get("trace")
    if t is None:
        return 0.0, 0
    return trace_lib.module_seconds(t, TICK_MODULE)


def tick_device_ms(record) -> Optional[float]:
    seconds, count = tick(record)
    return 1e3 * seconds / count if count else None


def tick_mfu_pct(record) -> Optional[float]:
    seconds, count = tick(record)
    if not count or seconds <= 0:
        return None
    per_row = flops.cifg_lm_forward_flops_per_token(
        record["embed_dim"], record["hidden"], record["vocab"])
    peak = peaks_for(record["device_kind"])["bf16_flops"]
    return 100.0 * record["decode_rows"] * per_row / (seconds * peak)


def median_ms(values) -> Optional[float]:
    return 1e3 * float(np.median(values)) if len(values) else None


def p95_ms(values) -> Optional[float]:
    return 1e3 * float(np.percentile(values, 95)) if len(values) else None


def max_ms(values) -> Optional[float]:
    return 1e3 * float(np.max(values)) if len(values) else None

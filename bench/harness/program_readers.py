"""Shared arithmetic of the per-layer readers that read the program's own
spans, counters and device scopes (``program_trace``).

Each takes the run's record and returns one number, or None where the run
was not traced or the program records nothing there (a program older than
its spans).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from bench.harness import program_trace as pt
from bench.harness import readers

# the host's staging of a round's cohort: the wait for the sampled ids, the
# gather and the transfer
STAGE_SPANS = ("fl.ids_wait", "fl.gather", "fl.put")


def stage_exposed_ms(record) -> Optional[float]:
    """Device idle time while the host stages a cohort, per staged round."""
    t = pt.load(record)
    if t is None or not t["devices"]:
        return None
    staged = len(pt.spans_named(t, "fl.put"))
    if not staged:
        return None
    return 1e3 * pt.idle_within(t, STAGE_SPANS) / staged


def scope_ms_per_round(record, scope: str) -> Optional[float]:
    """Device time under the scope ``scope``, per round of the window."""
    t = pt.load(record)
    if t is None or not pt.has_scopes(t) or not record.get("rounds"):
        return None
    return 1e3 * pt.scope_seconds(t, scope) / record["rounds"]


def queue_wait_p95_ms(record) -> Optional[float]:
    """p95 over the window's admissions of submit → start of admission."""
    t = pt.load(record)
    if t is None:
        return None
    return readers.p95_ms(pt.span_stat(t, "serve.admit", "queue_s"))


def span_median_ms(record, name: str) -> Optional[float]:
    t = pt.load(record)
    return None if t is None else readers.median_ms(pt.span_seconds(t, name))


def span_max_ms(record, name: str) -> Optional[float]:
    t = pt.load(record)
    return None if t is None else readers.max_ms(pt.span_seconds(t, name))


def tick_host_ms(record) -> Optional[float]:
    """Median host time of a decode tick outside its uploads and its wait
    for the tick's results."""
    t = pt.load(record)
    if t is None:
        return None
    return readers.median_ms(pt.self_seconds(
        t, "serve.tick", ("serve.upload", "serve.tick.sync")))


def tick_occupancy_pct(record) -> Optional[float]:
    """Live rows over computed rows of the window's decode ticks: the
    engine's ``decode_rows / (decode_ticks × max_slots)``, in %."""
    t = pt.load(record)
    if t is None:
        return None
    rows = pt.span_stat(t, "serve.tick", "rows")
    slots = pt.span_stat(t, "serve.tick", "slots")
    if not slots or len(rows) != len(slots):
        return None
    return 100.0 * float(np.sum(rows)) / float(np.sum(slots))

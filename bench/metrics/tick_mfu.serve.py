"""Model FLOPs of the active rows of the traced ticks over the ticks' device
time × the chip's bf16 peak, in %; moves serve_next_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.tick_mfu_pct(record)

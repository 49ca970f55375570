"""Model FLOPs of the window's rounds over window × chips × the chip's bf16
peak, in %; moves train_rounds_per_s.
"""
from bench.harness import readers


def read(record):
    return readers.train_mfu_pct(record)

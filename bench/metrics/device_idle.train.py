"""Share of the traced training window in which no operation ran on the device,
averaged over the chips, in %; moves train_rounds_per_s.
"""
from bench.harness import readers


def read(record):
    return readers.device_idle_pct(record)

"""Share of the traced serving window in which no operation ran on the device,
in %; moves serve_next_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.device_idle_pct(record)

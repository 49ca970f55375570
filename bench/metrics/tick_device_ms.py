"""Device time of one decode tick program, averaged over the traced ticks, in
ms; moves serve_next_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.tick_device_ms(record)

"""95th percentile of how late the open-loop generator submitted a session
after it fell due, in ms; moves serve_first_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.p95_ms(record['late_s'])

"""Median wall time of one admission (prefill, first suggestion, slot scatter)
over the window's admissions, in ms; moves serve_first_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.median_ms(record['admission_s'])

"""Longest gap between consecutive engine step returns while work was in
flight, in ms; moves serve_first_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.max_ms(record['stalls_s'])

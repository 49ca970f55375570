"""Median time an admission waits for its first suggestion and candidates
to reach the host (the program's serve.admit.sync span), in ms; moves
serve_first_p95_ms.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.span_median_ms(record, "serve.admit.sync")

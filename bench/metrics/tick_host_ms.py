"""Median host time of a decode tick outside its uploads and its wait for
the tick's results: dispatch and the per-slot bookkeeping (the program's
serve.tick span less serve.upload and serve.tick.sync), in ms; moves
serve_next_p95_ms.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.tick_host_ms(record)

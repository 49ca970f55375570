"""Longest host-to-device upload of an admission's or a decode tick's host
arrays in the window (the program's serve.upload span), in ms; moves
serve_first_p95_ms.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.span_max_ms(record, "serve.upload")

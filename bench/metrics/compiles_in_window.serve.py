"""Programs compiled or loaded from the persistent cache inside the serving
window; moves serve_first_p95_ms.
"""
from bench.harness import readers


def read(record):
    return readers.compiles(record)

"""Programs compiled or loaded from the persistent cache inside the training
window; moves train_rounds_per_s.
"""
from bench.harness import readers


def read(record):
    return readers.compiles(record)

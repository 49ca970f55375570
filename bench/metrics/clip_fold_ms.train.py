"""Device time of the ops traced under the program's clip_fold scope (clipping
each client's update and folding it into the round's sum), per round of the
window, in ms; moves train_rounds_per_s.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.scope_ms_per_round(record, "clip_fold")

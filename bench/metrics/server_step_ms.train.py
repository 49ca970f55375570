"""Device time of the ops traced under the program's server_step scope (noise,
mean and the server's momentum step), per round of the window, in ms; moves
train_rounds_per_s.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.scope_ms_per_round(record, "server_step")

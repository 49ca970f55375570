"""Device time of the ops traced under the program's client_sgd scope (the
clients' local SGD: batch gather, forward, backward and update, the output
head included), per round of the window, in ms; moves train_rounds_per_s.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.scope_ms_per_round(record, "client_sgd")

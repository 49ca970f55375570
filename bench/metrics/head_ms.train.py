"""Device time of the ops traced under the program's head scope (the tied-
embedding logits and the loss, with their gradients; it runs inside
client_sgd), per round of the window, in ms; moves train_rounds_per_s.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.scope_ms_per_round(record, "head")

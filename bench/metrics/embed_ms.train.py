"""Device time of the ops traced under the program's embed scope (the input
embedding's lookup and the table's gradient; it runs inside client_sgd), per
round of the window, in ms; moves train_rounds_per_s. None for a program
without that scope.
"""
from bench.harness import program_readers, program_trace


def read(record):
    names = program_trace.program_names()
    if names is None or "embed" not in names[1]:
        return None
    return program_readers.scope_ms_per_round(record, "embed")

"""Device idle time while the host waits for a round's sampled cohort ids,
gathers its examples and transfers them (the program's fl.ids_wait,
fl.gather and fl.put spans), per staged round, in ms; moves
train_rounds_per_s.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.stage_exposed_ms(record)

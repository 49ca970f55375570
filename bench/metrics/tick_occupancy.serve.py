"""Live rows over computed rows of the window's decode ticks, the engine's
decode_rows / (decode_ticks x max_slots) as counted on the program's
serve.tick spans, in %; moves serve_next_p95_ms.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.tick_occupancy_pct(record)

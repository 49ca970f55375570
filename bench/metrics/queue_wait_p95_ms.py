"""95th percentile, over the window's admissions, of how long a session
waited in ServeEngine's queue between submit and the start of its admission
(the queue_s counter on the program's serve.admit span), in ms; moves
serve_first_p95_ms.
"""
from bench.harness import program_readers


def read(record):
    return program_readers.queue_wait_p95_ms(record)

"""The program's trace points: host spans and device scopes, named here once.

Host spans are ``jax.profiler.TraceAnnotation`` events. They land on the
profiler's host plane, on the clock the device planes share, so a reader of
a trace can tell what the host was doing while the device sat idle. Their
keyword arguments (``round``, ``session``, ``tick`` and the engine's own
counters) come back as the event's stats. Nothing is recorded while no
profiler is running.

Device scopes are ``jax.named_scope`` names. They change no arithmetic: they
only prefix the ``op_name`` metadata of every HLO instruction traced under
them (``jit(f)/…/client_sgd/…/transpose(jvp(head))/dot_general``), which is
how a compiled program's device time is split by layer.
"""
from __future__ import annotations

import jax

# SimEngine's streamed round loop, per round
FL_SPANS = ("fl.sample",      # dispatch of the sampler program
            "fl.ids_wait",    # host blocked on the sampled cohort ids
            "fl.gather",      # host gather of the cohort's example rows
            "fl.put",         # the staged cohort's host-to-device transfers
            "fl.compute",     # dispatch of the round's compute program
            "fl.history")     # the closing fetch of the rounds' records
# ServeEngine: an admission (``serve.admit``) holding its upload, prefill
# dispatch and sync; a decode tick (``serve.tick``) holding its upload,
# sync and bookkeeping
SERVE_SPANS = ("serve.admit", "serve.upload", "serve.prefill",
               "serve.admit.sync", "serve.tick", "serve.tick.sync",
               "serve.tick.book")
SPANS = FL_SPANS + SERVE_SPANS

# device scopes of the DP-FedAvg round; ``embed`` (the input embedding and
# its gradient) and ``head`` run inside ``client_sgd``
SCOPES = ("sample", "client_sgd", "embed", "head", "clip_fold",
          "server_step")


def span(name: str, **ids):
    """Context manager: the host span ``name``, with ``ids`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **ids)


def scope(name: str):
    """Context manager: the device scope ``name`` (one of ``SCOPES``)."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)

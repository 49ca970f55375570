"""The input embedding's device scope as the benchmark reads it: the
compiled client gradient maps the embedding's lookup and its gradient to
``embed`` inside ``client_sgd``, and ``embed_ms.train`` reads that scope's
time per round, or nothing for a program without the scope."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.harness import program_trace as PT
from bench.harness import trace as T
from bench.harness.spec import reader
from repro.configs import get_config
from repro.models import build
from repro.utils import spans as P

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).parent / "fixtures"


def _hand_with_embed():
    """The hand trace with ``fusion.2`` (200 ns) moved under ``embed``."""
    t = T.normalize(json.loads(
        (FIXTURES / "trace_program_hand.json").read_text()))
    t["scopes"] = {m: {k: (tuple(v) if v is not None else None)
                       for k, v in table.items()}
                   for m, table in t["scopes"].items()}
    t["scopes"]["jit__compute_body(9)"]["fusion.2"] = ("client_sgd", "embed")
    return t


def test_embed_ms_reads_the_scope_per_round(monkeypatch):
    t = _hand_with_embed()
    monkeypatch.setattr(PT, "load", lambda record: t)
    read = reader(ROOT, "embed_ms.train")
    assert read({"rounds": 2}) == pytest.approx(100e-6)
    assert PT.scope_seconds(t, "client_sgd") == pytest.approx(600e-9)


def test_embed_ms_is_nothing_for_a_program_without_the_scope(monkeypatch):
    t = _hand_with_embed()
    monkeypatch.setattr(PT, "load", lambda record: t)
    scopes = tuple(s for s in P.SCOPES if s != "embed")
    monkeypatch.setattr(PT, "program_names", lambda: (P.SPANS, scopes))
    assert reader(ROOT, "embed_ms.train")({"rounds": 2}) is None


@pytest.mark.parametrize("path", ["fused", "seq"])
def test_the_embedding_and_its_gradient_map_to_the_embed_scope(path):
    cfg = get_config("gboard-cifg-lstm").with_(vocab=300, d_model=12,
                                               d_ff=20, cell_path=path)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, 5), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens, "mask": jnp.ones((2, 5))}

    def step(p, b):
        with P.scope("client_sgd"):
            return jax.grad(model.loss_fn)(p, b)

    text = jax.jit(step).lower(params, batch).compile().as_text()
    table = PT.scope_map(text, P.SCOPES)
    embedded = {name for name, v in table.items()
                if v and "embed" in v and "client_sgd" in v}
    ops = " ".join(re.findall(r'op_name="([^"]*embed[^"]*)"', text))
    assert embedded and "gather" in ops
    # the table's gradient: the kernel's matmul, or XLA's scatter-add
    assert ("dot_general" if path == "fused" else "scatter-add") in ops
    assert "head" not in {s for name in embedded for s in table[name]}

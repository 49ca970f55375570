"""The program's spans, counters and device scopes as the benchmark reads
them: real CPU profiles of a tiny ServeEngine and a tiny streamed SimEngine,
the HLO scope map, and the new reductions and readers on a hand fixture."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import program_readers as PR
from bench.harness import program_trace as PT
from bench.harness import trace as T
from bench.harness.context import SPANS as HARNESS_SPANS
from repro.utils import spans as P

FIXTURES = Path(__file__).parent / "fixtures"
# the spans each of the program's spans may run inside
PARENTS = {"serve.upload": ("serve.admit", "serve.tick"),
           "serve.prefill": ("serve.admit",),
           "serve.admit.sync": ("serve.admit",),
           "serve.tick.sync": ("serve.tick",),
           "serve.tick.book": ("serve.tick",)}


def hand():
    t = T.normalize(json.loads(
        (FIXTURES / "trace_program_hand.json").read_text()))
    t["scopes"] = {m: {k: (tuple(v) if v is not None else None)
                       for k, v in table.items()}
                   for m, table in t["scopes"].items()}
    return t


def profiled(tmp_path, body):
    """Run ``body`` under the profiler inside a ``bench.window`` span, as
    ``Context.traced`` does; return the trace directory."""
    tdir = tmp_path / "run-1" / "trace"
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tdir), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            body()
    finally:
        jax.profiler.stop_trace()
    return tdir


def assert_nested(spans):
    """Every span with a parent lies inside one of its parent spans."""
    for name, s, d, *_ in spans:
        parents = PARENTS.get(name)
        if parents is None:
            continue
        assert any(p[0] in parents and p[1] <= s and s + d <= p[1] + p[2]
                   for p in spans), (name, s, d)


# ------------------------------------------------------ real CPU profiles


@pytest.fixture(scope="module")
def lstm():
    from repro.configs import get_config
    from repro.models import build
    cfg = get_config("gboard-cifg-lstm").with_(vocab=300, d_model=24,
                                               d_ff=48)
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(1))


def test_serve_engine_spans_appear_nested_with_their_counters(lstm,
                                                               tmp_path):
    from repro.serve import NwpRequest, ServeEngine
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=4, top_k=3)
    for k in (3, 1, 2, 4, 2, 3):                    # one compile pass
        eng.submit(NwpRequest(prompt=(1, 5, 9, 4, 7)[:k + 1], steps=k))
    eng.run()
    ticks0, rows0 = eng.decode_ticks, eng.decode_rows

    def serve():
        for k in (3, 1, 2, 4, 2, 3):
            eng.submit(NwpRequest(prompt=(1, 5, 9, 4, 7)[:k + 1], steps=k))
        eng.run()

    tdir = profiled(tmp_path, serve)
    reduced = T.load(str(tdir), HARNESS_SPANS + P.SPANS)
    names = {s[0] for s in reduced["spans"]}
    assert set(P.SERVE_SPANS) <= names
    assert names <= set(HARNESS_SPANS + P.SPANS)
    assert_nested(reduced["spans"])

    spans = PT.host_spans(T.xplane_path(str(tdir)), P.SPANS)
    trace = {"devices": [], "window": reduced["window"], "spans": spans}
    admits = PT.spans_named(trace, "serve.admit")
    assert len(admits) == 6
    assert all(s[3]["session"].startswith("s") for s in admits)
    ticks = PT.spans_named(trace, "serve.tick")
    assert len(ticks) == eng.decode_ticks - ticks0
    assert sum(PT.span_stat(trace, "serve.tick", "rows")) == \
        eng.decode_rows - rows0
    assert set(PT.span_stat(trace, "serve.tick", "slots")) == {4}
    queued = PT.span_stat(trace, "serve.admit", "queue_s")
    assert len(queued) == 6 and min(queued) > 0
    # one upload per admission and one per decode tick
    assert len(PT.spans_named(trace, "serve.upload")) == 6 + len(ticks)


def test_streamed_sim_engine_spans_and_device_scopes(lstm, tmp_path):
    from repro.configs import ClientConfig, DPConfig
    from repro.data.corpus import BigramCorpus
    from repro.data.federated import FederatedDataset
    from repro.data.population_store import InMemoryPopulationStore
    from repro.fl.engine import SimEngine
    model, params = lstm
    ds = FederatedDataset(BigramCorpus(vocab_size=300, seed=0), n_users=40,
                          seq_len=8, sentences_per_user=6)
    eng = SimEngine(model, InMemoryPopulationStore.from_arrays(
                        ds.to_device_arrays()),
                    DPConfig(clients_per_round=16, noise_multiplier=0.5,
                             clip_norm=0.8),
                    ClientConfig(local_epochs=1, batch_size=4, lr=0.3),
                    n_local_batches=1, availability=0.8,
                    population_backend="streamed", sampler="sharded")
    state = eng.init_state(params, seed=0)
    state, _ = eng.run(state, 1)                    # compiles
    box = {}

    def rounds():
        box["state"], _ = eng.run(state, 2)

    tdir = profiled(tmp_path, rounds)
    path = T.xplane_path(str(tdir))
    spans = PT.host_spans(path, HARNESS_SPANS + P.SPANS)
    names = [s[0] for s in spans]
    assert set(P.FL_SPANS) <= set(names)
    assert set(names) <= set(HARNESS_SPANS + P.SPANS)
    trace = {"devices": [], "window": [spans[0][1], spans[0][1]
                                       + spans[0][2]], "spans": spans}
    for name in ("fl.sample", "fl.ids_wait", "fl.gather", "fl.put",
                 "fl.compute"):
        assert PT.span_stat(trace, name, "round") == [1, 2], name
    assert PT.span_stat(trace, "fl.history", "round") == [2]

    # the HLO the trace holds maps the round's instructions to every scope
    protos = PT.hlo_protos(path)
    compute = [m for m in protos if m.startswith("jit__compute_body(")]
    sample = [m for m in protos if m.startswith("jit__sample_body(")]
    assert compute and sample
    table = PT.scope_map(PT.hlo_text(protos[compute[0]]), P.SCOPES)
    seen = {sc for v in table.values() if v for sc in v}
    assert {"client_sgd", "head", "clip_fold", "server_step"} <= seen
    assert ("client_sgd", "head") in set(table.values())
    sampled = PT.scope_map(PT.hlo_text(protos[sample[0]]), P.SCOPES)
    assert {v for v in sampled.values() if v} == {("sample",)}


# ------------------------------------------------------------ scope map


def test_scope_map_of_a_compiled_function_with_a_transposed_scope():
    def loss(w, x):
        with jax.named_scope("head"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    def step(w, x):
        with jax.named_scope("client_sgd"):
            g = jax.grad(loss)(w, x)
        with jax.named_scope("server_step"):
            return w - 0.1 * g

    w = jnp.ones((16, 16))
    text = jax.jit(step).lower(w, w).compile().as_text()
    table = PT.scope_map(text, P.SCOPES)
    transposed = [line for line in text.splitlines()
                  if "transpose(jvp(head))" in line and " = " in line]
    assert transposed
    name = transposed[0].split(" = ")[0].strip().lstrip("%")
    if name.startswith("ROOT "):
        name = name[5:].lstrip("%")
    assert table[name] == ("client_sgd", "head")
    assert ("server_step",) in set(table.values())


def test_path_scopes_unwrap_transforms_and_keep_order():
    path = "jit(f)/while/body/client_sgd/vmap(transpose(jvp(head)))/dot"
    assert PT.path_scopes(path, P.SCOPES) == ("client_sgd", "head")
    assert PT.path_scopes("jit(f)/mul", P.SCOPES) == ()


def test_a_fusion_takes_its_roots_scope_and_loops_map_to_none():
    text = """HloModule m

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %a = f32[4]{0} exp(%p), metadata={op_name="jit(f)/client_sgd/exp"}
  ROOT %b = f32[4]{0} add(%a, %a), metadata={op_name="jit(f)/head/add"}
}

%body (q: f32[4]) -> f32[4] {
  %q = f32[4]{0} parameter(0)
  ROOT %fusion.1 = f32[4]{0} fusion(%q), kind=kLoop, calls=%fused
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %c = f32[4]{0} copy(%x)
  ROOT %while.2 = f32[4]{0} while(%c), condition=%cond, body=%body, metadata={op_name="jit(f)/client_sgd/while"}
}
"""
    table = PT.scope_map(text, P.SCOPES)
    assert table["fusion.1"] == ("head",)
    assert table["a"] == ("client_sgd",)
    assert table["while.2"] is None
    assert "c" not in table and "x" not in table


# ------------------------------------------------- reductions by hand


def test_span_seconds_count_the_window_only():
    t = hand()
    assert PT.span_seconds(t, "fl.put") == pytest.approx([1500e-9])
    assert PT.span_seconds(t, "serve.upload") == pytest.approx(
        [200e-9, 100e-9])


def test_self_seconds_leave_out_the_named_children():
    # tick 1: 1000 - 200 (upload) - 300 (sync); tick 2: 500 - 100
    got = PT.self_seconds(hand(), "serve.tick",
                          ("serve.upload", "serve.tick.sync"))
    assert got == pytest.approx([500e-9, 400e-9])


def test_idle_within_the_staging_spans():
    # staging holds [500, 4500); the device runs [1000, 1800) and
    # [4000, 4500): idle 500 + 2200 ns
    assert PT.idle_within(hand(), PR.STAGE_SPANS) == pytest.approx(2700e-9)
    assert PT.idle_within(hand(), ("fl.gather",)) == pytest.approx(1000e-9)


def test_scope_seconds_nest_and_leave_out_loops():
    t = hand()
    assert PT.scope_seconds(t, "client_sgd") == pytest.approx(600e-9)
    assert PT.scope_seconds(t, "head") == pytest.approx(400e-9)
    assert PT.scope_seconds(t, "clip_fold") == pytest.approx(100e-9)
    assert PT.scope_seconds(t, "server_step") == pytest.approx(500e-9)
    row = PT.scope_table(t)["jit__compute_body(9)"]
    assert row["total"] == pytest.approx(1300e-9)       # while.1 left out
    assert row["scoped"] == pytest.approx(1200e-9)
    assert row["unscoped"] == {"copy.4": pytest.approx(100e-9)}
    assert PT.scope_table(t)["jit__tick(7)"]["scoped"] == 0.0
    assert PT.has_scopes(t)
    assert not PT.has_scopes(dict(t, scopes={}))


def test_readers_on_the_hand_trace(monkeypatch):
    t = hand()
    monkeypatch.setattr(PT, "load", lambda record: t)
    record = {"rounds": 2}
    assert PR.stage_exposed_ms(record) == pytest.approx(2700e-6)
    assert PR.scope_ms_per_round(record, "head") == pytest.approx(200e-6)
    assert PR.queue_wait_p95_ms(record) == pytest.approx(
        1e3 * np.percentile([0.004, 0.002], 95))
    assert PR.span_median_ms(record, "serve.admit.sync") == pytest.approx(
        200e-6)
    assert PR.span_max_ms(record, "serve.upload") == pytest.approx(200e-6)
    assert PR.tick_host_ms(record) == pytest.approx(450e-6)
    assert PR.tick_occupancy_pct(record) == pytest.approx(100 * 4 / 32)


def test_a_program_without_spans_reads_as_nothing(monkeypatch):
    t = hand()
    t["spans"] = [s for s in t["spans"] if s[0] == "bench.window"]
    t["scopes"] = {}
    monkeypatch.setattr(PT, "load", lambda record: t)
    record = {"rounds": 2}
    assert PR.stage_exposed_ms(record) is None
    assert PR.scope_ms_per_round(record, "head") is None
    assert PR.queue_wait_p95_ms(record) is None
    assert PR.tick_host_ms(record) is None
    assert PR.tick_occupancy_pct(record) is None
    monkeypatch.setattr(PT, "load", lambda record: None)
    assert PR.span_max_ms(record, "serve.upload") is None


def test_load_finds_the_run_by_its_window(tmp_path, monkeypatch):
    def body():
        with P.span("serve.tick", tick=1, rows=2, slots=8):
            jnp.ones(4).block_until_ready()

    tdir = profiled(tmp_path, body)
    reduced = T.load(str(tdir), HARNESS_SPANS)
    record = {"trace": reduced}
    t = PT.load(record, roots=(tmp_path,))
    assert t is not None and PT.span_stat(t, "serve.tick", "rows") == [2]
    assert PT.load(record, roots=()) is t           # kept on the record
    other = {"trace": dict(reduced, window=[0.0, 1.0])}
    assert PT.load(other, roots=(tmp_path,)) is None
    assert PT.load({"trace": None}, roots=(tmp_path,)) is None
    monkeypatch.setattr(PT, "program_names", lambda: None)
    assert PT.load({"trace": reduced}, roots=(tmp_path,)) is None

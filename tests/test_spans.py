"""The program's trace points and counters: ``repro.utils.spans`` names
every span and scope once, and ``ServeEngine`` counts what the benchmark's
readers take from its spans (queue wait, decode ticks, live rows)."""
import time

import jax
import pytest

from repro.configs import get_config
from repro.models import build
from repro.serve import NwpRequest, ServeEngine
from repro.serve.engine import ADMISSION_TIMES_KEPT
from repro.utils import spans


@pytest.fixture(scope="module")
def lstm():
    cfg = get_config("gboard-cifg-lstm").with_(vocab=300, d_model=32,
                                               d_ff=64)
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_names_are_unique():
    assert len(set(spans.SPANS)) == len(spans.SPANS)
    assert len(set(spans.SCOPES)) == len(spans.SCOPES)


def test_scope_refuses_a_name_it_does_not_know():
    with pytest.raises(ValueError, match="unknown device scope"):
        spans.scope("clientsgd")
    with spans.scope("client_sgd"):
        pass


def test_queue_wait_and_decode_counters_on_a_scripted_run(lstm):
    """Two slots, three sessions submitted at once (3, 1 and 2
    suggestions). Step 1 admits the first two; the second finishes at
    admission, and the third waits for step 2 (slots are offered once a
    step). Decode ticks: step 1 with one live row, step 2 with two."""
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    sids = [eng.submit(NwpRequest(prompt=(1, 5, 9), steps=k))
            for k in (3, 1, 2)]
    t_submitted = time.perf_counter()
    assert eng.step()
    t_step1 = time.perf_counter()
    assert eng.decode_ticks == 1 and eng.decode_rows == 1
    assert not eng.step()
    assert eng.decode_ticks == 2 and eng.decode_rows == 3
    assert not eng.step()                 # nothing live: no decode tick
    assert eng.decode_ticks == 2 and eng.decode_rows == 3
    res = [eng.result(s) for s in sids]
    assert [r.admit_tick for r in res] == [1, 1, 2]
    assert [len(r.tokens) for r in res] == [3, 1, 2]
    assert res[2].queue_s >= t_step1 - t_submitted
    assert res[2].queue_s > max(res[0].queue_s, res[1].queue_s)
    for r in res:
        assert 0.0 < r.queue_s <= r.latency_s


def test_a_session_with_no_steps_never_queues(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    sid = eng.submit(NwpRequest(prompt=(1, 5), steps=0))
    assert eng.result(sid).queue_s == 0.0
    assert eng.decode_ticks == 0 and eng.decode_rows == 0


def test_admission_times_are_bounded(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    assert eng._admission_times.maxlen == ADMISSION_TIMES_KEPT == 1 << 16
    for _ in range(3):
        eng.submit(NwpRequest(prompt=(1, 5), steps=1))
    eng.run()
    assert len(eng.admission_times_s) == 3

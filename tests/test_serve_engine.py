"""Continuous-batching serving engine: token-for-token parity with the
single-request reference path (greedy + seeded temperature, interleaved
admission/eviction, across checkpoint hot-swap boundaries), top-k candidate
shape/ordering, TTL eviction, queueing/slot reuse, and the per-row cache
layout contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build
from repro.serve import (NwpRequest, ServeEngine, reference_generate,
                         validate_cache_layout)
from repro.train import checkpoint


@pytest.fixture(scope="module")
def lstm():
    cfg = get_config("gboard-cifg-lstm").with_(vocab=300, d_model=32,
                                               d_ff=64)
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def params_b(lstm):
    model, _ = lstm
    return model.init(jax.random.PRNGKey(42))


def _requests(rng, n, vocab=300, temperature=0.0, seed0=100):
    reqs = []
    for i in range(n):
        prompt = tuple(int(t) for t in
                       rng.integers(4, vocab, size=int(rng.integers(2, 7))))
        reqs.append(NwpRequest(prompt=prompt,
                               steps=int(rng.integers(1, 7)),
                               temperature=temperature,
                               seed=seed0 + i if temperature > 0 else None))
    return reqs


def _assert_matches_reference(model, params, engine, reqs, sids, top_k=3):
    for req, sid in zip(reqs, sids):
        res = engine.result(sid)
        toks, cands = reference_generate(
            model, params, req.prompt, req.steps,
            temperature=req.temperature, seed=req.seed, top_k=top_k)
        assert res.tokens == toks, sid
        np.testing.assert_array_equal(res.candidates, cands)


def test_engine_matches_reference_greedy(lstm):
    """Slots << sessions: queueing + slot reuse must not change any
    session's tokens or candidate strip."""
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    reqs = _requests(np.random.default_rng(0), 6)
    sids = [eng.submit(r) for r in reqs]
    res = eng.run()
    assert len(res) == 6
    assert all(r.status == "done" for r in res.values())
    _assert_matches_reference(model, params, eng, reqs, sids)


def test_engine_matches_reference_temperature(lstm):
    """Seeded-temperature sessions: per-session streams are independent of
    batch composition, deterministic across runs, and distinct across
    seeds."""
    model, params = lstm
    reqs = _requests(np.random.default_rng(1), 5, temperature=0.8)
    outs = []
    for _ in range(2):  # engine determinism: identical second run
        eng = ServeEngine(model, params, max_slots=3, top_k=3)
        sids = [eng.submit(r) for r in reqs]
        eng.run()
        _assert_matches_reference(model, params, eng, reqs, sids)
        outs.append([eng.result(s).tokens for s in sids])
    assert outs[0] == outs[1]

    # same prompt, different seeds → different streams (overwhelmingly)
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    a = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=8, temperature=0.9,
                              seed=7))
    b = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=8, temperature=0.9,
                              seed=8))
    eng.run()
    assert eng.result(a).tokens != eng.result(b).tokens


def test_interleaved_admission_parity(lstm):
    """Sessions submitted mid-flight (while others are at different decode
    depths) still match the reference exactly — admission timing is not
    allowed to leak into the tokens."""
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=3, top_k=3)
    rng = np.random.default_rng(2)
    first = _requests(rng, 3, temperature=0.6, seed0=200)
    sids = [eng.submit(r) for r in first]
    eng.step()
    eng.step()
    late = _requests(rng, 4, temperature=0.6, seed0=300)
    sids += [eng.submit(r) for r in late]
    eng.step()
    more = _requests(rng, 2)
    sids += [eng.submit(r) for r in more]
    eng.run()
    _assert_matches_reference(model, params, eng, first + late + more, sids)


def test_fifo_admission_and_slot_reuse(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=1, top_k=2)
    reqs = [NwpRequest(prompt=(2, 10 + i), steps=3) for i in range(4)]
    sids = [eng.submit(r) for r in reqs]
    eng.run()
    admits = [eng.result(s).admit_tick for s in sids]
    assert admits == sorted(admits)  # FIFO through the single slot
    assert all(eng.result(s).status == "done" for s in sids)
    _assert_matches_reference(model, params, eng, reqs, sids, top_k=2)


def test_topk_candidates_shape_and_ordering(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=4)
    sid = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=5))
    narrow = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=5, top_k=2))
    eng.run()
    res = eng.result(sid)
    assert res.candidates.shape == (5, 4)
    # greedy token is always candidate 0; candidates are rank-ordered by
    # logit (reference comparison pins the full ordering)
    np.testing.assert_array_equal(res.candidates[:, 0],
                                  np.asarray(res.tokens))
    assert all(len(set(row)) == 4 for row in res.candidates)
    _, ref_cands = reference_generate(model, params, (2, 5, 9), 5, top_k=4)
    np.testing.assert_array_equal(res.candidates, ref_cands)
    # per-request top_k narrows the strip without recompiling the engine
    assert eng.result(narrow).candidates.shape == (5, 2)
    np.testing.assert_array_equal(eng.result(narrow).candidates,
                                  ref_cands[:, :2])


def test_hot_swap_atomicity_and_parity(lstm, params_b):
    """Promote new params with sessions in flight: nobody dropped, each
    session's version trail is monotone with at most one transition, and
    tokens match a reference that swaps checkpoints at the same index."""
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=4, top_k=3)
    reqs = [NwpRequest(prompt=(2, 5, 9 + i), steps=8,
                       temperature=0.7 if i % 2 else 0.0,
                       seed=50 + i if i % 2 else None) for i in range(4)]
    sids = [eng.submit(r) for r in reqs]
    for _ in range(3):
        eng.step()
    assert eng.active_sessions == 4
    assert eng.swap_params(params_b) == 1
    post = NwpRequest(prompt=(2, 77), steps=4)
    post_sid = eng.submit(post)
    eng.run()

    for req, sid in zip(reqs, sids):
        res = eng.result(sid)
        assert res.status == "done"  # zero dropped across the swap
        vs = res.params_versions
        assert list(vs) == sorted(vs) and set(vs) <= {0, 1}
        assert vs[0] == 0 and vs[-1] == 1  # swap landed mid-session
        swap_at = vs.index(1)
        toks, cands = reference_generate(
            model, params, req.prompt, req.steps,
            temperature=req.temperature, seed=req.seed, top_k=3,
            swaps=[(swap_at, params_b)])
        assert res.tokens == toks
        np.testing.assert_array_equal(res.candidates, cands)

    # a session admitted after the swap is pure-v1, prefill included
    res = eng.result(post_sid)
    assert set(res.params_versions) == {1}
    toks, _ = reference_generate(model, params, post.prompt, post.steps,
                                 swaps=[(0, params_b)])
    assert res.tokens == toks


def test_hot_swap_from_checkpoint_file(tmp_path, lstm, params_b):
    """The production promotion path: a freshly trained round lands as a
    checkpoint file and is swapped in without dropping sessions."""
    model, params = lstm
    ck = tmp_path / "round_next.msgpack"
    checkpoint.save(ck, params_b, meta={"arch": model.cfg.name})
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    sid = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=6))
    eng.step()
    assert eng.load_checkpoint(ck) == 1
    eng.run()
    res = eng.result(sid)
    assert res.status == "done"
    swap_at = res.params_versions.index(1)
    toks, _ = reference_generate(model, params, (2, 5, 9), 6,
                                 swaps=[(swap_at, params_b)])
    assert res.tokens == toks


def test_ttl_eviction_frees_slot(lstm):
    """A session that exceeds its tick budget is evicted with its partial
    output (a reference prefix), and its slot is handed to the queue."""
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=1, top_k=3)
    hog = eng.submit(NwpRequest(prompt=(2, 5), steps=50, ttl_ticks=3))
    nxt = eng.submit(NwpRequest(prompt=(2, 9), steps=2))
    eng.run()
    res = eng.result(hog)
    assert res.status == "evicted"
    assert len(res.tokens) == 4  # token0 at admission + 3 decode ticks
    ref_toks, _ = reference_generate(model, params, (2, 5), 4)
    assert res.tokens == ref_toks
    assert eng.result(nxt).status == "done"
    assert len(eng.result(nxt).tokens) == 2


def test_steps0_completes_immediately(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    sid = eng.submit(NwpRequest(prompt=(2, 5, 9), steps=0))
    res = eng.result(sid)
    assert res.status == "done" and res.tokens == ()
    assert res.candidates.shape == (0, 3)
    assert res.sequence == (2, 5, 9)  # exactly the prompt
    assert eng.in_flight == 0  # never took a slot or a tick


def test_submit_validation(lstm):
    model, params = lstm
    eng = ServeEngine(model, params, max_slots=2, top_k=3)
    with pytest.raises(ValueError, match="seed"):
        eng.submit(NwpRequest(prompt=(2, 5), steps=3, temperature=0.8))
    with pytest.raises(ValueError, match="steps"):
        eng.submit(NwpRequest(prompt=(2, 5), steps=-1))
    with pytest.raises(ValueError, match="prompt tokens"):
        eng.submit(NwpRequest(prompt=(2, 999), steps=1))
    with pytest.raises(ValueError, match="top_k"):
        eng.submit(NwpRequest(prompt=(2, 5), steps=1, top_k=7))
    sid = eng.submit(NwpRequest(prompt=(2, 5), steps=0, session_id="dup"))
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(NwpRequest(prompt=(2, 5), steps=1, session_id="dup"))
    assert sid == "dup"


def test_cache_layout_contract_rejected(lstm):
    """Ring-buffer KV models share a scalar position across the batch —
    the engine must refuse them with a clear error, not corrupt slots."""
    cfg = get_config("granite-3-2b").reduced()
    model = build(cfg)
    with pytest.raises(ValueError, match="continuous-batching"):
        validate_cache_layout(model, max_slots=4, max_len=16)
    with pytest.raises(ValueError, match="per-row"):
        ServeEngine(model, {}, max_slots=4)
    # the paper's model passes the same validation the engine runs
    lstm_model, _ = lstm
    cache = validate_cache_layout(lstm_model, max_slots=4, max_len=16)
    assert all(np.shape(leaf)[0] == 4
               for leaf in jax.tree_util.tree_leaves(cache))


def test_full_width_rows_independent_of_batch():
    """At the paper model's widths (embedding 96, hidden 256, vocab 10,000)
    a session's prefill and decode results are bitwise independent of the
    batch it shares and of bucket padding — what bucketed admission and
    the reference parity rest on."""
    model = build(get_config("gboard-cifg-lstm"))
    params = model.init(jax.random.PRNGKey(0))
    assert ServeEngine(model, params, max_slots=16).bucketed_admission
    toks = jax.random.randint(jax.random.PRNGKey(1), (16, 5), 4, 10_000)
    exact = jax.jit(model.prefill)(params, {"tokens": toks[:1, :3]})
    padded = jax.jit(model.prefill)(
        params, {"tokens": toks[:1, :4], "length": jnp.array([3])})
    logits, cache = jax.jit(model.prefill)(params, {"tokens": toks})
    one = jax.jit(model.decode_step)(params, toks[:1, 0],
                                     jax.tree_util.tree_map(
                                         lambda a: a[:1], cache))
    full = jax.jit(model.decode_step)(params, toks[:, 0], cache)
    for a, b in ((exact, padded), (one, jax.tree_util.tree_map(
            lambda x: x[:1], full))):
        for x, y in zip(jax.tree_util.tree_leaves(a),
                        jax.tree_util.tree_leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _with_length_prefill(model, error):
    """``model`` whose length-aware prefill raises ``error`` instead."""
    def prefill(params, batch, **kw):
        if "length" in batch:
            raise error
        return model.prefill(params, batch, **kw)
    return model._replace(prefill=prefill)


def test_length_probe_falls_back_on_rejected_key(lstm):
    """A model that rejects the ``length`` batch key is served with
    exact-length admission, token for token like the reference."""
    model, params = lstm
    plain = _with_length_prefill(
        model, KeyError("unsupported batch key 'length'"))
    eng = ServeEngine(plain, params, max_slots=2, top_k=3)
    assert not eng.bucketed_admission
    assert ServeEngine(model, params, max_slots=2).bucketed_admission
    reqs = _requests(np.random.default_rng(3), 3)
    sids = [eng.submit(r) for r in reqs]
    eng.run()
    _assert_matches_reference(model, params, eng, reqs, sids)


def test_length_probe_propagates_other_errors(lstm):
    """Only a rejection of the ``length`` key is a fallback: a compile or
    runtime failure of the length-aware prefill surfaces at construction."""
    model, params = lstm
    broken = _with_length_prefill(model, RuntimeError("kernel refused"))
    with pytest.raises(RuntimeError, match="kernel refused"):
        ServeEngine(broken, params, max_slots=2)
    unrelated = _with_length_prefill(model, ValueError("bad tile shape"))
    with pytest.raises(ValueError, match="bad tile shape"):
        ServeEngine(unrelated, params, max_slots=2)


def test_engine_constructor_validation(lstm):
    model, params = lstm
    with pytest.raises(ValueError, match="max_slots"):
        ServeEngine(model, params, max_slots=0)
    with pytest.raises(ValueError, match="top_k"):
        ServeEngine(model, params, max_slots=2, top_k=0)

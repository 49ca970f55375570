"""Continuous-batching NWP serving engine with a device-resident session
cache.

The paper's artifact is a *deployed* next-word-prediction model: a DP-FedAvg
round trains server-side, gets promoted to serving, and answers suggestion-
strip queries from millions of phones. This module is that traffic path at
simulation scale:

* **Fixed-slot session cache** — the decode state for up to ``max_slots``
  concurrent sessions lives device-resident, slot-major: one row per
  session in every cache leaf (for the CIFG-LSTM that is the tiny ``(h, c)``
  recurrent pair plus a position — ~``2·d_ff`` floats per session, so
  thousands of sessions fit per chip). Admission scatters a freshly
  prefilled session into a free slot; completion/timeout frees it. The
  decode program never changes shape, so it compiles exactly once.
* **Continuous batching** — every engine tick runs ONE ``decode_step`` over
  the full slot axis. Sessions at different depths coexist in the batch;
  finished sessions hand their slot to queued requests between ticks (no
  barrier on the slowest request, the classic continuous-batching win).
* **Per-session sampling** — token *t* of a session draws from
  ``fold_in(session_key, t)`` (`repro.serve.sampling`), so results are
  independent of slot index, batch composition, and admission timing:
  the engine is **token-for-token equal to the single-request reference
  path** (`repro.serve.reference`), which is the tested contract.
* **Top-k candidates** — each emitted position carries the ranked
  ``top_k`` candidate ids for the suggestion strip (``lax.top_k`` fused
  into the tick).
* **Bucketed admission** — prefill prompt lengths are padded up to powers
  of two (the model's length-aware prefill gathers the state at the true
  length, so results are bitwise identical to the exact-length prefill):
  the admission path compiles O(log max_prompt) prefill programs instead of
  one per distinct length, which is what keeps admission p99 bounded under
  organic length mixes. Models without length-aware prefill (detected by a
  behavioral probe at construction) fall back to exact-length admission.
* **Atomic checkpoint hot-swap** — :meth:`swap_params` /
  :meth:`load_checkpoint` promote a new checkpoint between ticks: one
  host-side reference assignment, in-flight sessions keep their slots and
  state. A tick is a single jitted call closed over a single params pytree,
  so no session ever computes a step from a mix of two checkpoints; each
  emitted token records the params version that produced it
  (``SessionResult.params_versions``), which is how the hot-swap drill
  audits atomicity.

The engine requires a *continuous-batching capable* cache layout: every
``init_cache`` leaf per-row (leading dim = batch) so sessions can be
scattered/gathered by slot — see the serving contract note in
`repro.models.api`. Ring-buffer KV models (shared scalar position) are
rejected with a clear error.
"""
from __future__ import annotations

import collections
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.api import Model
from repro.serve import sampling
from repro.serve.frontend import (NwpRequest, RequestQueue, SessionResult,
                                  _Session, make_session_key, new_session_id)
from repro.train import checkpoint as checkpoint_lib
from repro.utils.spans import span

# admission times kept (the newest), far more than any measuring window
# holds, so a long-lived server's list stays bounded
ADMISSION_TIMES_KEPT = 1 << 16


def validate_cache_layout(model: Model, max_slots: int, max_len: int):
    """Build the probe cache and enforce the per-row serving contract.
    Returns the (zero-initialized) slot cache on success."""
    cache = model.init_cache(max_slots, max_len)
    bad = [(path, leaf.shape)
           for path, leaf in
           jax.tree_util.tree_flatten_with_path(cache)[0]
           if np.ndim(leaf) < 1 or np.shape(leaf)[0] != max_slots]
    if bad:
        detail = ", ".join(f"{jax.tree_util.keystr(p)}: shape {s}"
                           for p, s in bad)
        raise ValueError(
            f"model '{model.cfg.name}' is not continuous-batching capable: "
            f"the serving engine scatters per-session state by slot, so "
            f"every decode-cache leaf must be per-row (leading dim = "
            f"max_slots={max_slots}); offending leaves: {detail}. "
            f"Recurrent-state models (the paper's CIFG-LSTM) satisfy this; "
            f"shared ring-buffer KV caches do not (yet).")
    return cache


class ServeEngine:
    """Session-oriented continuous-batching decode loop over
    ``model.decode_step``.

    Single-threaded host driver: call :meth:`submit` to enqueue sessions,
    :meth:`step` to run one admission+decode tick (or :meth:`run` to
    drain), :meth:`pop_completed` to collect finished sessions. Not
    thread-safe — callers interleave submits/swaps between ticks, which is
    exactly what makes the hot swap atomic.
    """

    def __init__(self, model: Model, params, *, max_slots: int = 256,
                 top_k: int = 3, max_len: int = 64,
                 default_ttl_ticks: Optional[int] = None):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if top_k < 1 or top_k > model.cfg.vocab:
            raise ValueError(f"top_k must be in [1, vocab="
                             f"{model.cfg.vocab}], got {top_k}")
        self.model = model
        self.max_slots = max_slots
        self.top_k = top_k
        self.vocab = model.cfg.vocab
        self.default_ttl_ticks = default_ttl_ticks

        self._params = jax.tree_util.tree_map(jnp.asarray, params)
        self._params_version = 0
        self._swap_log: List[tuple] = []   # (tick, new_version)

        self._cache = validate_cache_layout(model, max_slots, max_len)
        # host-side per-slot control state, shipped to device every tick
        self._slots: List[Optional[_Session]] = [None] * max_slots
        self._cur_tok = np.zeros((max_slots,), np.int32)
        self._keys = np.zeros((max_slots, 2), np.uint32)
        self._ts = np.zeros((max_slots,), np.int32)
        self._temps = np.zeros((max_slots,), np.float32)

        self._queue = RequestQueue()
        self._completed: List[SessionResult] = []
        self._results: Dict[str, SessionResult] = {}
        self._ticks = 0          # step() calls (admission opportunities)
        self._decode_ticks = 0   # ticks that actually ran a decode batch
        self._decode_rows = 0    # live slots summed over the decode ticks

        vocab, K = self.vocab, self.top_k

        def _prefill(p, toks):
            last, sub = model.prefill(p, {"tokens": toks})
            return last[:, :vocab], sub

        def _prefill_len(p, toks, length):
            last, sub = model.prefill(p, {"tokens": toks, "length": length})
            return last[:, :vocab], sub

        def _admission_sample(lg, key, temp):
            tok = sampling.sample_tokens(
                lg, key[None], jnp.zeros((1,), jnp.int32), temp[None])
            return tok[0], sampling.topk_ids(lg, K)[0]

        def _admit(cache, slot, sub):
            return jax.tree_util.tree_map(
                lambda buf, row: buf.at[slot].set(row[0]), cache, sub)

        def _tick(p, cache, toks, keys, ts, temps):
            logits, cache = model.decode_step(p, toks, cache)
            lg = logits[:, :vocab]
            nxt = sampling.sample_tokens(lg, keys, ts, temps)
            return nxt, sampling.topk_ids(lg, K), cache

        self._prefill_j = jax.jit(_prefill)
        self._prefill_len_j = jax.jit(_prefill_len)
        self._admission_sample_j = jax.jit(_admission_sample)
        self._admit_j = jax.jit(_admit, donate_argnums=(0,))
        self._tick_j = jax.jit(_tick, donate_argnums=(1,))
        # admission latency per admitted session (includes the prefill jit
        # compile on a fresh *bucketed* length — the long tail bucketing
        # exists to bound); bench_serve.py reports p50/p99 from this
        self._admission_times: collections.deque = collections.deque(
            maxlen=ADMISSION_TIMES_KEPT)
        self._bucketed = self._probe_length_support()

    # ------------------------------------------------------------- frontend

    @property
    def params_version(self) -> int:
        return self._params_version

    @property
    def in_flight(self) -> int:
        """Sessions admitted to a slot or waiting in the queue."""
        return len(self._queue) + self.active_sessions

    @property
    def active_sessions(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def queued(self) -> int:
        return len(self._queue)

    @property
    def ticks(self) -> int:
        return self._ticks

    @property
    def decode_ticks(self) -> int:
        """Ticks that ran the decode program (some slot was live)."""
        return self._decode_ticks

    @property
    def decode_rows(self) -> int:
        """Live slots summed over the decode ticks; over
        ``decode_ticks × max_slots`` it is the tick's occupancy."""
        return self._decode_rows

    def submit(self, request: NwpRequest) -> str:
        """Validate + enqueue a session; returns its session id. A
        ``steps=0`` request completes immediately with exactly the prompt
        (no slot, no decode — the suggestion strip asked for nothing)."""
        request.validate(self.vocab, self.top_k)
        sid = request.session_id or new_session_id()
        if sid in self._results or any(
                s is not None and s.session_id == sid for s in self._slots):
            raise ValueError(f"duplicate session_id {sid!r}")
        sess = _Session(request=request, session_id=sid,
                        key=make_session_key(request.seed),
                        submit_tick=self._ticks,
                        submit_time=time.perf_counter())
        if request.steps == 0:
            sess.admit_tick = self._ticks
            self._finalize(sess, "done", slot=None)
            return sid
        self._queue.push(sess)
        return sid

    def pop_completed(self) -> List[SessionResult]:
        out, self._completed = self._completed, []
        return out

    def result(self, session_id: str) -> SessionResult:
        return self._results[session_id]

    # ------------------------------------------------------------- hot swap

    def swap_params(self, new_params) -> int:
        """Atomically promote ``new_params`` for every *subsequent* prefill
        and decode tick. In-flight sessions keep their slots and recurrent
        state; tokens already emitted keep their version label. Returns the
        new params version."""
        self._params = jax.tree_util.tree_map(jnp.asarray, new_params)
        self._params_version += 1
        self._swap_log.append((self._ticks, self._params_version))
        return self._params_version

    def load_checkpoint(self, path) -> int:
        """Hot-swap from a checkpoint file (the DP-trained round promoted
        to serving): fully loaded + converted host-side, then published in
        one :meth:`swap_params` call."""
        params, _meta = checkpoint_lib.load(path)
        return self.swap_params(params)

    # ------------------------------------------------------------- the loop

    def step(self) -> bool:
        """One engine tick: admit from the queue into free slots, then run
        one batched decode step over all slots. Returns True while there is
        work in flight."""
        self._ticks += 1
        for slot in range(self.max_slots):
            if not len(self._queue):
                break
            if self._slots[slot] is None:
                self._admit(slot, self._queue.pop())
        live = self.active_sessions
        if live == 0:
            return len(self._queue) > 0
        self._decode_ticks += 1
        self._decode_rows += live
        with span("serve.tick", tick=self._decode_ticks, rows=live,
                  slots=self.max_slots):
            self._decode()
        return self.in_flight > 0

    def _decode(self) -> None:
        """One batched decode step over all slots, and its bookkeeping."""
        with span("serve.upload"):
            control = (jnp.asarray(self._cur_tok), jnp.asarray(self._keys),
                       jnp.asarray(self._ts), jnp.asarray(self._temps))
        nxt, cands, self._cache = self._tick_j(self._params, self._cache,
                                               *control)
        with span("serve.tick.sync"):
            nxt = np.asarray(nxt)
            cands = np.asarray(cands)
        with span("serve.tick.book"):
            for slot, sess in enumerate(self._slots):
                if sess is None:
                    continue
                self._record_token(sess, int(nxt[slot]), cands[slot])
                self._cur_tok[slot] = nxt[slot]
                self._ts[slot] += 1
                sess.ticks_in_slot += 1
                if len(sess.tokens) >= sess.request.steps:
                    self._finalize(sess, "done", slot=slot)
                elif (self._ttl(sess)
                      and sess.ticks_in_slot >= self._ttl(sess)):
                    self._finalize(sess, "evicted", slot=slot)

    def run(self, max_ticks: int = 100_000) -> Dict[str, SessionResult]:
        """Drain queue + slots; returns {session_id: result} for every
        session finished during this call."""
        before = dict(self._results)
        for _ in range(max_ticks):
            if not self.step():
                break
        else:
            raise RuntimeError(f"run() did not drain in {max_ticks} ticks")
        return {k: v for k, v in self._results.items() if k not in before}

    # ------------------------------------------------------------ internals

    def _ttl(self, sess: _Session) -> Optional[int]:
        ttl = sess.request.ttl_ticks
        return ttl if ttl is not None else self.default_ttl_ticks

    def _probe_length_support(self) -> bool:
        """Behavioral probe for the length-aware prefill contract: a model
        supports bucket-padded admission iff prefilling ``[t]`` unpadded and
        ``[t, 0]`` with ``length=[1]`` agree *bitwise* (logits and every
        cache leaf). A model that rejects the ``"length"`` batch key (a
        ``KeyError``, ``TypeError`` or ``ValueError`` naming it) or silently
        ignores it fails the probe, and admission falls back to exact-length
        prefills (one jit compile per distinct prompt length). Any other
        error — a kernel the backend refuses, a runtime fault — propagates."""
        ref_lg, ref_sub = self._prefill_j(self._params,
                                          jnp.zeros((1, 1), jnp.int32))
        try:
            lg, sub = self._prefill_len_j(
                self._params, jnp.zeros((1, 2), jnp.int32),
                jnp.ones((1,), jnp.int32))
        except (KeyError, TypeError, ValueError) as e:
            if "length" not in str(e):
                raise
            return False
        ref_leaves = jax.tree_util.tree_leaves((ref_lg, ref_sub))
        leaves = jax.tree_util.tree_leaves((lg, sub))
        return len(ref_leaves) == len(leaves) and all(
            a.shape == b.shape and bool(jnp.all(a == b))
            for a, b in zip(ref_leaves, leaves))

    @property
    def admission_times_s(self) -> tuple:
        """Wall-clock seconds per admission (prefill + first-token sample +
        slot scatter, synced on the emitted token), in admission order."""
        return tuple(self._admission_times)

    @property
    def bucketed_admission(self) -> bool:
        """True when the construction-time probe validated the model's
        length-aware prefill and admissions pad to power-of-two buckets."""
        return self._bucketed

    def _admit(self, slot: int, sess: _Session) -> None:
        """Prefill the prompt (current params), scatter the session state
        into ``slot``, and emit token 0 from the prefill logits. Prompt
        lengths are bucketed to powers of two (right-padded, with the true
        length gathered inside the model's length-aware prefill) so a fresh
        length only compiles when it crosses a power of two — token-for-
        token identical to the exact-length prefill, which is what
        :meth:`_probe_length_support` guarantees up front."""
        t0 = time.perf_counter()
        sess.queue_s = t0 - sess.submit_time
        raw = np.asarray(sess.request.prompt, np.int32)
        L = int(raw.shape[0])
        bucketed = self._bucketed and L > 1
        with span("serve.admit", session=sess.session_id,
                  queue_s=sess.queue_s):
            with span("serve.upload"):
                if bucketed:
                    padded = np.zeros((1, 1 << (L - 1).bit_length()),
                                      np.int32)
                    padded[0, :L] = raw
                    toks = jnp.asarray(padded)
                    length = jnp.full((1,), L, jnp.int32)
                else:
                    toks = jnp.asarray(raw)[None, :]
                key = jnp.asarray(sess.key)
                temp = jnp.asarray(sess.request.temperature, jnp.float32)
                slot_d = jnp.asarray(slot)
            with span("serve.prefill"):
                if bucketed:
                    lg, sub = self._prefill_len_j(self._params, toks, length)
                else:
                    lg, sub = self._prefill_j(self._params, toks)
                tok0, cands0 = self._admission_sample_j(lg, key, temp)
                self._cache = self._admit_j(self._cache, slot_d, sub)
            sess.admit_tick = self._ticks
            self._slots[slot] = sess
            self._keys[slot] = sess.key
            self._temps[slot] = sess.request.temperature
            with span("serve.admit.sync"):
                tok0, cands0 = int(tok0), np.asarray(cands0)
            self._record_token(sess, tok0, cands0)
        self._admission_times.append(time.perf_counter() - t0)
        self._cur_tok[slot] = sess.tokens[-1]
        self._ts[slot] = 1
        if len(sess.tokens) >= sess.request.steps:
            self._finalize(sess, "done", slot=slot)

    def _record_token(self, sess: _Session, tok: int, cands) -> None:
        sess.tokens.append(tok)
        sess.candidates.append(np.asarray(cands, np.int32))
        sess.versions.append(self._params_version)

    def _finalize(self, sess: _Session, status: str,
                  slot: Optional[int]) -> None:
        if slot is not None:
            self._slots[slot] = None
            self._temps[slot] = 0.0
            self._ts[slot] = 0
        k = sess.request.top_k or self.top_k
        cands = (np.stack(sess.candidates)[:, :k] if sess.candidates
                 else np.zeros((0, k), np.int32))
        res = SessionResult(
            session_id=sess.session_id,
            prompt=tuple(int(t) for t in sess.request.prompt),
            tokens=tuple(sess.tokens),
            candidates=cands,
            status=status,
            params_versions=tuple(sess.versions),
            submit_tick=sess.submit_tick,
            admit_tick=sess.admit_tick,
            finish_tick=self._ticks,
            latency_s=time.perf_counter() - sess.submit_time,
            queue_s=sess.queue_s)
        self._results[sess.session_id] = res
        self._completed.append(res)

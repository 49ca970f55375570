"""Driver of the keyboard serving cells: ``ServeEngine`` fed open loop.

Set-up makes the weights and the sessions from the seed, builds the engine,
and warms every program the window will use: the unpadded one-token prefill,
one prefill per power-of-two prompt bucket up to the mix's longest prompt,
the admission sampler, the slot scatter and the decode tick. Then it runs
``gc.collect(); gc.freeze()`` so that no collection of set-up's objects
lands in the window.

The window submits each session when it falls due and otherwise steps the
engine; with nothing in flight it waits for the next arrival. Arrivals stop
at ``--seconds``; the engine then drains what is in flight, and those
sessions' latencies count their wait. A session's token ``i`` is emitted in
the engine step ``admit_tick + max(i - 1, 0)`` (tokens 0 and 1 come in the
admission step, the first from the prefill and the second from that step's
decode tick), so the harness reads each suggestion's time from its own table
of step end times and nothing inside the engine.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import numpy as np

from bench.harness import program, traffic
from bench.harness.compare import Checks
from bench.harness.context import Context, Result
from bench.harness.device import memory_peak_bytes
from bench.harness.weights import cifg_params
from bench.reference import cifg_lstm as ref

SPIN_S = 0.0005      # the generator sleeps until this close to a due time


def build_engine(ctx: Context, params):
    from repro.serve import ServeEngine
    e = ctx.config["engine"]
    return ServeEngine(program.model(ctx.config["model"]), params,
                       max_slots=int(e["max_slots"]), top_k=int(e["top_k"]),
                       max_len=int(e["max_len"]))


def warm(engine, mix: Dict, vocab: int, seed: int) -> None:
    """Run one session per admission program, each to the longest
    suggestion count, so the window compiles nothing."""
    from repro.serve import NwpRequest
    rng = np.random.default_rng([int(seed), 7])
    steps = int(mix["suggestions"]["max"])
    for L in traffic.bucket_lengths(int(mix["prompt_len"]["max"])):
        prompt = (traffic.BOS,) + tuple(
            int(t) for t in rng.integers(traffic.FIRST_WORD, vocab, L - 1))
        engine.submit(NwpRequest(prompt=prompt, steps=steps))
    engine.run()
    engine.pop_completed()


def run(ctx: Context, alter=None, keep=None) -> Result:
    """One run of the cell. ``alter`` is called with the engine before the
    window, to plant a fault underneath (the fault tests); ``keep``, a dict,
    receives the weights, the sessions and the finished results (the tools
    that read the control)."""
    from repro.serve import NwpRequest
    cfg, mix = ctx.config, ctx.mix
    vocab = cfg["model"]["vocab"]
    params = cifg_params(ctx.seed, cfg["model"], ctx.devices[0])
    sess = traffic.sessions(mix, ctx.seed, ctx.seconds, vocab)
    engine = build_engine(ctx, params)
    warm(engine, mix, vocab, ctx.seed)
    if alter is not None:
        alter(engine)
    n = len(sess["due"])
    reqs = [NwpRequest(prompt=tuple(int(t) for t in p), steps=int(k))
            for p, k in zip(sess["prompts"], sess["steps"])]
    sids: List[str] = [""] * n
    submit_at = np.zeros(n)
    step_end: Dict[int, float] = {}
    stalls: List[float] = []
    adm0 = len(engine.admission_times_s)
    gc.collect()
    gc.freeze()
    setup_s = ctx.setup_seconds()
    compiles0 = ctx.compiles.programs
    holder: Dict = {}
    due = sess["due"]
    with ctx.traced(holder):
        t0 = time.perf_counter()
        i = 0
        last_end, busy = None, False
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                with jax.profiler.TraceAnnotation("engine.submit"):
                    sids[i] = engine.submit(reqs[i])
                submit_at[i] = time.perf_counter() - t0
                i += 1
            if engine.in_flight:
                with jax.profiler.TraceAnnotation("engine.step"):
                    busy_after = engine.step()
                end = time.perf_counter() - t0
                step_end[engine.ticks] = end
                if busy and last_end is not None:
                    stalls.append(end - last_end)
                last_end, busy = end, busy_after
            elif i < n:
                with jax.profiler.TraceAnnotation("generator.wait"):
                    wait = due[i] - (time.perf_counter() - t0) - SPIN_S
                    if wait > 0:
                        time.sleep(wait)
                    while time.perf_counter() - t0 < due[i]:
                        pass
                busy = False
            else:
                break
        drained = time.perf_counter() - t0
    compiles = ctx.compiles.programs - compiles0
    gc.unfreeze()
    peak = memory_peak_bytes(ctx.devices)

    first, nexts, failed, decode_rows = [], [], 0, 0
    results = []
    for k in range(n):
        res = engine.result(sids[k])
        ntok = len(res.tokens)
        if (res.status != "done" or ntok != int(sess["steps"][k])
                or res.finish_tick != res.admit_tick + max(ntok - 2, 0)):
            failed += 1
            continue
        results.append((k, res))
        times = [step_end[res.admit_tick + max(j - 1, 0)]
                 for j in range(ntok)]
        first.append(times[0] - due[k])
        nexts.extend(np.diff(times).tolist())
        decode_rows += ntok - 1
    admissions = list(engine.admission_times_s[adm0:])
    engine = None
    gc.collect()
    if keep is not None:
        keep.update(params=params, sessions=sess, results=results)

    checks = Checks()
    for name, value in served_gaps(ctx, params, sess, results).items():
        checks.add(name, value, cfg["limits"][name])
    checks.add("failed_sessions", failed, 0)
    record = {
        "kind": "serve", "sessions": n, "window_s": ctx.seconds,
        "drained_s": drained, "device_kind": ctx.devices[0].device_kind,
        "admission_s": admissions,
        "late_s": (submit_at - due).tolist(), "stalls_s": stalls,
        "decode_rows": decode_rows, "embed_dim": cfg["model"]["embed_dim"],
        "hidden": cfg["model"]["hidden"], "vocab": vocab,
        "compiles_in_window": compiles, "trace": holder.get("trace"),
    }
    e2e = {"setup_s": setup_s}
    if first:
        e2e["serve_first_p95_ms"] = float(np.percentile(first, 95)) * 1e3
    if nexts:
        e2e["serve_next_p95_ms"] = float(np.percentile(nexts, 95)) * 1e3
    return Result(
        end_to_end=e2e, record=record, checks=checks, attempted=n,
        failed=failed, memory_peak_bytes=peak, trace=holder.get("trace"),
        notes=[f"serve: set-up {setup_s:.3f} s",
               f"serve: {n} sessions due in {ctx.seconds} s, drained at "
               f"{drained:.3f} s, {len(step_end)} steps, compiles in window "
               f"{compiles}"] + (
            [f"WARNING: {compiles} programs were compiled or loaded inside "
             "the window"] if compiles else []))


def sample(ctx: Context, results: List, n: int) -> List:
    """``n`` finished sessions drawn from the seed, the longest among them."""
    if not results:
        return []
    rng = np.random.default_rng([int(ctx.seed), 11])
    longest = max(range(len(results)),
                  key=lambda j: (len(results[j][1].prompt)
                                 + len(results[j][1].tokens)))
    pick = set(rng.choice(len(results), size=min(n, len(results)),
                          replace=False).tolist())
    pick.add(longest)
    return [results[j] for j in sorted(pick)]


def served_gaps(ctx: Context, params, sess: Dict, results: List,
                precision: str = "f32") -> Dict[str, float]:
    """The widest gaps, over a sample of finished sessions, by which a
    served token's logit lies below the f32 reference's best at its position
    (``logit_gap``), and a served candidate's below the reference's best of
    its rank (``candidate_gap``). With a lower ``precision`` it reads the
    control instead: at each of the same positions, the gaps of the token
    and the candidates the lower-precision reference ranks first."""
    picked = sample(ctx, results, int(ctx.mix["checked_sessions"]))
    if not picked:
        return {"logit_gap": float("inf"), "candidate_gap": float("inf")}
    prompts = [np.asarray(r.prompt, np.int32) for _, r in picked]
    served = [np.asarray(r.tokens, np.int32) for _, r in picked]
    cands = [np.asarray(r.candidates, np.int32) for _, r in picked]
    tok, cand = ref.served_gaps(
        jax.device_get(params), prompts, served, cands,
        ctx.config["model"]["vocab"], precision=precision,
        pick="served" if precision == "f32" else "argmax")
    return {"logit_gap": float(np.max(tok)),
            "candidate_gap": float(np.max(cand))}

#!/usr/bin/env python3
"""Bring-up smoke test on one TPU chip: the DP-FedAvg engine and the NWP
serving engine at the paper model's full width (``gboard-cifg-lstm``: vocab
10,000, embedding 96, CIFG hidden 256, about 1.26M parameters), driven
through their normal entry points. Weights are random and the corpus is
generated from ``--seed``; everything is written under ``--out``.

    python3 chip_smoke.py                 # one chip, every phase below
    python3 chip_smoke.py --four-chips    # four chips, the sharded phase only

Phases, in order, all in this one process (the chip belongs to it):

1. device: a TPU is required; there is no CPU fallback.
2. kernels: the fused ``dp_clip`` clip-accumulate against the pytree
   reference, and the client loss value-and-grad on the fused Pallas cell,
   head (``kernels.lm_head_xent``) and embedding gradient
   (``kernels.embed_grad``) against the plain autodiff scan, ``lm_loss``
   and XLA's scatter, at full width in bf16 compute. Both compiled programs must
   contain ``tpu_custom_call``.
3. train, device backend: ``repro.launch.train.main`` with 10,000 users,
   1,000 clients per round, 4 rounds, noise multiplier 0.3.
4. train, paper deployment: ``SimEngine.run`` on the streamed population
   backend with the sharded sampler at N = 10^6, cohort 1,000, dropout 0.3
   and report goal 800.
5. serve: ``ServeEngine`` over phase 3's checkpoint, 32 greedy sessions on
   16 slots; 4 of them are checked token for token against
   ``repro.serve.reference_generate``.

``--four-chips`` runs phase 4's configuration on a (pod 2, data 2) cohort
mesh and on device 0 alone, and requires bitwise-equal params and history
(the canonical-reduction contract of ``repro.fl.reduction``).

Each phase prints its compile and steady seconds; they are informational.
Any failure raises, so the exit code is non-zero. The last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench.harness.clock import CompileCounter  # noqa: E402
from repro.configs import ClientConfig, DPConfig, get_config  # noqa: E402
from repro.core.clipping import clip_accumulate_tree  # noqa: E402
from repro.data.corpus import BigramCorpus  # noqa: E402
from repro.data.federated import FederatedDataset  # noqa: E402
from repro.data.population_store import (  # noqa: E402
    InMemoryPopulationStore, ReplicatedPopulationStore)
from repro.data.tokenizer import BOS  # noqa: E402
from repro.fl.engine import SimEngine  # noqa: E402
from repro.fl.faults import FaultConfig  # noqa: E402
from repro.kernels.dp_clip import ops as dp_clip_ops  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.models import build  # noqa: E402
from repro.serve import (NwpRequest, ServeEngine,  # noqa: E402
                         reference_generate)
from repro.train import checkpoint  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

ARCH = "gboard-cifg-lstm"
CLIP_NORM = 0.8
NOISE = 0.3
# fused vs tree clip-accumulate: both f32, differing only in the association
# of the sum of squares, so the accumulators agree to a few f32 ulps
CLIP_TOL = 1e-6
# fused vs ref client loss/grad in bf16 compute: the fused cell's reverse
# pass runs in f32 while autodiff of the ref scan runs bf16 matmuls, so they
# agree to bf16 precision; each grad leaf's max |diff| is taken relative to
# that leaf's max |ref grad|
LOSS_TOL = 1e-2
GRAD_RTOL = 5e-2
TRAIN_USERS = 10_000
TRAIN_COHORT = 1000
DEPLOY_USERS = 10 ** 6
DEPLOY_BASE = 1000
DEPLOY_COHORT = 1000
DEPLOY_GOAL = 800


@contextlib.contextmanager
def _phase(name, clock):
    print(f"[{name}] start", flush=True)
    c0, t0 = clock.seconds, time.perf_counter()
    yield
    wall, comp = time.perf_counter() - t0, clock.seconds - c0
    print(f"[{name}] ok compile_s={comp:.2f} steady_s={wall - comp:.2f}",
          flush=True)


def _pallas_compiled(fn, *args):
    """``jax.jit(fn)`` compiled for ``args``; fails unless a Pallas kernel
    was compiled into it rather than interpreted."""
    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{getattr(fn, '__name__', fn)}: no "
                             "tpu_custom_call in the compiled program")
    return compiled


def _max_abs(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def kernels_phase(seed: int) -> None:
    cfg = get_config(ARCH)
    fused, ref = (build(cfg.with_(cell_path=p)) for p in ("fused", "ref"))
    k_init, k_acc, k_delta, k_tok = jax.random.split(
        jax.random.PRNGKey(seed), 4)
    params = fused.init(k_init)
    leaves, treedef = jax.tree_util.tree_flatten(params)

    def normal_tree(key, scale):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten([scale * jax.random.normal(k, l.shape)
                                  for k, l in zip(keys, leaves)])

    # a delta far above the clip norm, so the clip factor is live
    acc, delta = normal_tree(k_acc, 0.1), normal_tree(k_delta, 0.01)
    clip_fused = _pallas_compiled(
        lambda a, d: dp_clip_ops.clip_accumulate(a, d, CLIP_NORM), acc, delta)
    acc_f, norm_f = clip_fused(acc, delta)
    acc_t, norm_t, _ = jax.jit(lambda a, d: clip_accumulate_tree(
        a, d, CLIP_NORM, clip_path="tree"))(acc, delta)
    acc_diff = max(_max_abs(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(acc_f), jax.tree_util.tree_leaves(acc_t)))
    norm_rel = abs(float(norm_f) - float(norm_t)) / float(norm_t)
    print(f"  dp_clip clip_accumulate vs tree: acc max_abs_diff={acc_diff:.3e}"
          f" (tol {CLIP_TOL:.0e}), norm {float(norm_f):.6f} rel_diff="
          f"{norm_rel:.3e}")
    _check(acc_diff <= CLIP_TOL and norm_rel <= CLIP_TOL,
           "fused dp_clip disagrees with the tree reference")

    B, S = 10, 16
    toks = jax.random.randint(k_tok, (B, S + 1), 4, cfg.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:],
             "mask": jnp.ones((B, S), jnp.float32)}
    step_fused = _pallas_compiled(jax.value_and_grad(fused.loss_fn),
                                  params, batch)
    loss_f, g_f = step_fused(params, batch)
    loss_r, g_r = jax.jit(jax.value_and_grad(ref.loss_fn))(params, batch)
    loss_diff = abs(float(loss_f) - float(loss_r))
    worst = 0.0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_f)[0],
                            jax.tree_util.tree_leaves(g_r)):
        rel = _max_abs(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-30)
        print(f"  grad {jax.tree_util.keystr(path)}: max_abs_diff="
              f"{_max_abs(a, b):.3e} rel={rel:.3e}")
        worst = max(worst, rel)
    print(f"  client step fused vs ref: loss {float(loss_f):.6f} vs "
          f"{float(loss_r):.6f} diff={loss_diff:.3e} (tol {LOSS_TOL:.0e}), "
          f"worst grad rel diff={worst:.3e} (tol {GRAD_RTOL:.0e})")
    _check(loss_diff <= LOSS_TOL and worst <= GRAD_RTOL,
           "fused-cell client step disagrees with the autodiff reference")


def train_phase(out: Path, seed: int) -> Path:
    rounds = 4
    trainer = train.main([
        "--arch", ARCH, "--backend", "engine", "--n-users", str(TRAIN_USERS),
        "--clients-per-round", str(TRAIN_COHORT), "--rounds", str(rounds),
        "--rounds-per-call", "2", "--noise-multiplier", str(NOISE),
        "--seed", str(seed), "--out", str(out)])
    hist = trainer.state.history
    _check(len(hist) == rounds, f"expected {rounds} rounds, got {len(hist)}")
    for rec in hist:
        print(f"  round {rec['round']}: loss={rec['loss']:.6f} "
              f"update_norm={rec['mean_update_norm']:.6f} "
              f"clients={rec['n_clients']}")
        _check(np.isfinite(rec["loss"]) and np.isfinite(
            rec["mean_update_norm"]), f"non-finite round {rec}")
    ck = out / f"{ARCH}_r{rounds}.msgpack"
    _check(ck.exists(), f"no checkpoint at {ck}")
    return ck


def _deployment_base(seed: int):
    model = build(get_config(ARCH))
    corpus = BigramCorpus(vocab_size=model.cfg.vocab, seed=seed)
    ds = FederatedDataset(corpus, n_users=DEPLOY_BASE, seq_len=16,
                          sentences_per_user=20)
    return model, InMemoryPopulationStore.from_dataset(ds)


def _deployment_run(model, base, seed: int, rounds: int, *,
                    num_pods: int = 1, num_shards: int = 1):
    """The paper deployment: N = 10^6 users replicated from the base
    store, cohort 1,000 over-selected against dropout 0.3 to a report goal
    of 800, streamed population, sharded sampler."""
    dp = DPConfig(clients_per_round=DEPLOY_COHORT, noise_multiplier=NOISE,
                  clip_norm=CLIP_NORM, server_opt="momentum", server_lr=0.5,
                  server_momentum=0.9)
    eng = SimEngine(model, ReplicatedPopulationStore(base, DEPLOY_USERS), dp,
                    ClientConfig(local_epochs=1, batch_size=10, lr=0.3),
                    n_local_batches=2, availability=0.5,
                    population_backend="streamed", sampler="sharded",
                    fault_config=FaultConfig(seed=seed, dropout_prob=0.3,
                                             report_goal=DEPLOY_GOAL),
                    num_pods=num_pods, num_shards=num_shards)
    state = eng.init_state(model.init(jax.random.PRNGKey(seed + 1)),
                           seed=seed)
    state, hist = eng.run(state, rounds)
    return jax.device_get(state), hist


def deployment_phase(seed: int) -> None:
    model, base = _deployment_base(seed)
    state, hist = _deployment_run(model, base, seed, rounds=4)
    for i in range(len(hist["loss"])):
        print(f"  round {i + 1}: loss={hist['loss'][i]:.6f} update_norm="
              f"{hist['mean_update_norm'][i]:.6f} selected="
              f"{hist['n_selected'][i]} reported={hist['n_reported'][i]} "
              f"accepted={hist['n_clients'][i]} "
              f"committed={bool(hist['committed'][i])}")
    _check(all(np.all(np.isfinite(np.asarray(l)))
               for l in jax.tree_util.tree_leaves(state.params)),
           "non-finite params after the deployment rounds")
    _check(np.all(np.isfinite(hist["loss"])), "non-finite round loss")
    _check(int(np.sum(hist["committed"])) >= 1, "no round committed")


def serve_phase(ckpt: Path, seed: int) -> None:
    model = build(get_config(ARCH))
    params, _ = checkpoint.load(ckpt)
    params = jax.tree_util.tree_map(jnp.asarray, params)
    engine = ServeEngine(model, params, max_slots=16, top_k=3)
    _check(engine.bucketed_admission, "bucketed admission is off: the "
           "length-aware prefill probe failed")
    rng = np.random.default_rng(seed)
    reqs = [NwpRequest(prompt=(BOS,) + tuple(
                int(t) for t in rng.integers(4, model.cfg.vocab, size=n - 1)),
                       steps=8)
            for n in rng.integers(2, 13, size=32)]
    sids = [engine.submit(r) for r in reqs]
    engine.run()
    done = sum(engine.result(s).status == "done"
               and len(engine.result(s).tokens) == 8 for s in sids)
    print(f"  sessions completed {done}/{len(sids)} on 16 slots, bucketed "
          f"admission {engine.bucketed_admission}, ticks {engine.ticks}")
    _check(done == len(sids), "not every session completed")
    for req, sid in list(zip(reqs, sids))[:4]:
        toks, cands = reference_generate(model, params, req.prompt,
                                         req.steps, top_k=3)
        res = engine.result(sid)
        same = res.tokens == toks and np.array_equal(res.candidates, cands)
        print(f"  prompt len {len(req.prompt)}: engine {list(res.tokens)} "
              f"reference {list(toks)} equal={same}")
        _check(same, f"session {sid} differs from the reference")


def sharded_parity_phase(seed: int) -> None:
    """(pod 2, data 2) on four chips vs one device: bitwise, round by
    round, the canonical-reduction contract."""
    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, found {len(jax.devices())}")
    model, base = _deployment_base(seed)
    runs = {}
    for pods, shards in ((2, 2), (1, 1)):
        runs[pods, shards] = _deployment_run(model, base, seed, rounds=3,
                                             num_pods=pods,
                                             num_shards=shards)
        print(f"  pods {pods} shards {shards}: committed "
              f"{runs[pods, shards][1]['committed'].tolist()} loss "
              f"{runs[pods, shards][1]['loss'].tolist()}", flush=True)
    (s4, h4), (s1, h1) = runs[2, 2], runs[1, 1]
    mismatched = []
    for name, a_tree, b_tree in (("params", s4.params, s1.params),
                                 ("history", h4, h1)):
        for (path, a), b in zip(
                jax.tree_util.tree_flatten_with_path(a_tree)[0],
                jax.tree_util.tree_leaves(b_tree)):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                mismatched.append(
                    f"{name}{jax.tree_util.keystr(path)} max_abs_diff="
                    f"{_max_abs(a, b):.3e}")
    print(f"  (pods 2, shards 2) vs (shards 1): bitwise equal="
          f"{not mismatched}" + "".join(f"\n    {m}" for m in mismatched))
    _check(not mismatched, "sharded run is not bitwise equal to one device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the (pod 2, data 2) vs one-device parity "
                         "phase on four chips")
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="directory for the checkpoint and run outputs")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but jax.devices()[0] is "
                         f"on platform {dev.platform!r}")
    print(f"device: kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)
    print(f"compilation cache: {enable_compile_cache()}", flush=True)
    clock = CompileCounter()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    if args.four_chips:
        with _phase("sharded parity, 4 chips", clock):
            sharded_parity_phase(args.seed)
    else:
        with _phase("kernels", clock):
            kernels_phase(args.seed)
        with _phase("train, device backend", clock):
            ckpt = train_phase(out, args.seed)
        with _phase("train, paper deployment", clock):
            deployment_phase(args.seed)
        with _phase("serve", clock):
            serve_phase(ckpt, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()

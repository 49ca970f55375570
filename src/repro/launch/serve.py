"""Serving CLI: a thin frontend over the continuous-batching engine
(`repro.serve.ServeEngine`) — session admission, batched decode with the
device-resident state cache, top-k suggestion candidates, optional
checkpoint hot-swap drill.

    PYTHONPATH=src python -m repro.launch.serve --arch gboard-cifg-lstm \
        --ckpt experiments/runs/gboard-cifg-lstm_r100.msgpack --steps 8

``--reference`` runs the fixed one-shot batch path (:func:`generate`)
instead of the engine; it is kept as the pre-engine batch reference and the
regression surface for the historical decode bugs (``steps=0`` emitting a
token, ``temperature>0`` with no key crashing, batch rows sharing one
sampling stream).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data.tokenizer import BOS
from repro.models import build
from repro.serve import NwpRequest, ServeEngine
from repro.serve.sampling import sample_tokens
from repro.train import checkpoint
from repro.utils.compile_cache import enable_compile_cache


def generate(model, params, prompts: jnp.ndarray, steps: int,
             temperature: float = 0.0, key=None, max_len: int = None):
    """prompts: (B, S0) int32 → (B, S0+steps). Greedy if temperature=0.

    ``steps=0`` returns exactly the prompts. Temperature sampling requires
    ``key``; each batch row samples from its own stream
    (``fold_in(key, row)`` is the row's session key — see
    `repro.serve.sampling` for the schedule the serving engine shares).
    """
    if steps < 0:
        raise ValueError(f"steps must be >= 0, got {steps}")
    if temperature > 0.0 and key is None:
        raise ValueError(
            "generate(temperature>0) needs a PRNG key: pass "
            "key=jax.random.PRNGKey(seed) so sampling is reproducible "
            "(greedy decoding, temperature=0, needs none)")
    B, S0 = prompts.shape
    max_len = max_len or (S0 + steps)
    last, cache = model.prefill(params, {"tokens": prompts}, max_len=max_len)
    if steps == 0:
        return prompts
    vocab = model.cfg.vocab
    if key is None:
        row_keys = jnp.zeros((B, 2), jnp.uint32)  # greedy: keys unused
    else:
        row_keys = jax.vmap(jax.random.fold_in, (None, 0))(
            key, jnp.arange(B))
    temps = jnp.full((B,), temperature, jnp.float32)
    decode_j = jax.jit(model.decode_step)
    sample_j = jax.jit(sample_tokens)

    def pick(logits, t):
        return sample_j(logits[:, :vocab], row_keys,
                        jnp.full((B,), t, jnp.int32), temps)

    toks = [pick(last, 0)]
    for t in range(1, steps):
        logits, cache = decode_j(params, toks[-1], cache)
        toks.append(pick(logits, t))
    return jnp.concatenate([prompts, jnp.stack(toks, axis=1)], axis=1)


def main(argv=None):
    """Parse ``argv`` (default: ``sys.argv[1:]``) and serve the sessions."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gboard-cifg-lstm")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=4,
                    help="number of sessions to submit")
    ap.add_argument("--slots", type=int, default=None,
                    help="engine decode slots (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (session i uses seed+i)")
    ap.add_argument("--top-k", type=int, default=3,
                    help="suggestion-strip candidates per position")
    ap.add_argument("--vocab", type=int, default=None,
                    help="override the lstm vocab (default: the config's)")
    ap.add_argument("--hot-swap", default=None, metavar="CKPT",
                    help="promote this checkpoint mid-run (hot-swap demo)")
    ap.add_argument("--reference", action="store_true",
                    help="run the one-shot batch reference path instead "
                         "of the continuous-batching engine")
    ap.add_argument("--cell-path", default=None,
                    choices=["auto", "fused", "seq", "ref"],
                    help="lstm recurrence implementation (decode_step runs "
                         "the same fused Pallas cell as training)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "lstm" and args.vocab is not None:
        cfg = cfg.with_(vocab=args.vocab)
    if args.cell_path is not None:
        cfg = cfg.with_(cell_path=args.cell_path)
    model = build(cfg)
    if args.ckpt:
        params, meta = checkpoint.load(args.ckpt)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        print(f"loaded checkpoint ({meta})")
    else:
        params = model.init(jax.random.PRNGKey(0))
        print("serving a randomly initialized model (pass --ckpt)")

    key = jax.random.PRNGKey(args.seed + 1)
    prompts = np.full((args.batch, args.prompt_len), BOS, np.int32)
    prompts[:, 1:] = np.asarray(
        jax.random.randint(key, (args.batch, args.prompt_len - 1), 4,
                           cfg.vocab))

    if args.reference:
        out = generate(model, params, jnp.asarray(prompts), args.steps,
                       args.temperature,
                       jax.random.PRNGKey(args.seed)
                       if args.temperature > 0 else None)
        for row in np.asarray(out):
            print("prompt:", row[:args.prompt_len].tolist(),
                  "→ continuation:", row[args.prompt_len:].tolist())
        return

    engine = ServeEngine(model, params, max_slots=args.slots or args.batch,
                         top_k=args.top_k)
    sids = [engine.submit(NwpRequest(
        prompt=tuple(int(t) for t in prompts[i]), steps=args.steps,
        temperature=args.temperature,
        seed=args.seed + i if args.temperature > 0 else None))
        for i in range(args.batch)]
    if args.hot_swap:
        for _ in range(max(1, args.steps // 2)):
            engine.step()
        v = engine.load_checkpoint(args.hot_swap)
        print(f"hot-swapped to {args.hot_swap} (params v{v}, "
              f"{engine.active_sessions} sessions in flight)")
    engine.run()
    for sid in sids:
        r = engine.result(sid)
        print(f"{sid} [{r.status}] prompt: {list(r.prompt)} → "
              f"continuation: {list(r.tokens)} "
              f"(strip: {r.candidates[-1].tolist() if len(r.tokens) else []})")


if __name__ == "__main__":
    main()

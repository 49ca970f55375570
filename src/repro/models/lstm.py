"""The paper's production NWP model (§III-A): single-layer CIFG-LSTM [SSB14]
with tied input-embedding/output-projection, ~1.3M parameters, 10k vocab.

CIFG couples the input and forget gates (i = 1 − f), so there are three gate
matrices (f, o, g). A linear projection maps the hidden state back to the
embedding dimension so the tied embedding can produce logits.

Hot-path structure (PR 5 — the time-fused client step): the gate matrix is
split into ``w_x (d, 3h)`` and ``w_h (h, 3h)`` so the input projection for
*all* timesteps is one large ``(B·S, d) @ (d, 3h)`` GEMM hoisted out of the
time scan (it is h-independent); the scan step only does the small
``h @ w_h`` matmul plus the gate nonlinearities and state update.
``cfg.cell_path`` selects the implementation of the recurrence and of the
training loss's output head; ``"fused"`` means the Pallas kernels of this
model:

* ``"fused"`` — `kernels.cifg_cell.cifg_sequence` with the Pallas cell
  kernel as the per-step forward (compiled on TPU, interpreter elsewhere)
  and the time-fused custom backward (gate recompute + ``dw_h`` reduction
  batched over time outside the reverse scan); and ``loss_fn``'s head is
  `kernels.lm_head_xent`: the tied-embedding logits, the softmax
  cross-entropy and its backward in one kernel pair, so the (rows, Vpad)
  f32 logits never leave VMEM. ``forward`` (eval, secret-sharer scoring)
  and serving keep the plain `lm_logits` head. The input embedding of
  ``loss_fn`` and ``forward`` is `kernels.embed_grad.embed_lookup`: the
  same ``take`` forward, and a one-hot matmul kernel for the table's
  gradient in place of XLA's scatter-add; serving keeps `embed_tokens`;
* ``"seq"`` — the same time-fused sequence op with the pure-jnp cell as
  the per-step forward (the fast path on non-TPU backends, where the
  Pallas interpreter would run the cell per step);
* ``"ref"`` — the pre-split-style plain ``lax.scan`` over the jnp cell
  with ordinary jax autodiff through the scan — the validated reference;
  ``"seq"`` and ``"ref"`` take the head's loss as `lm_logits` followed by
  `layers.lm_loss`, and the input embedding as `embed_tokens`, under
  autodiff;
* ``"auto"`` (default) — ``"fused"`` on TPU (recurrence, head and input
  embedding),
  ``"seq"`` elsewhere.

Old ``w_gates`` checkpoints load through the one-shot migration shim in
`repro.train.checkpoint`.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.cifg_cell import (cifg_cell_ref, cifg_sequence,
                                     cifg_states, cifg_step)
from repro.kernels.embed_grad.ops import embed_lookup
from repro.kernels.lm_head_xent.ops import lm_head_xent
from repro.models import layers as L
from repro.models.api import Model
from repro.models.embed import embed_tokens, embedding_init, lm_logits
from repro.utils.spans import scope

CELL_PATHS = ("auto", "fused", "seq", "ref")


def resolve_cell_path(cfg: ModelConfig) -> str:
    """``"auto"`` → compiled Pallas kernels on TPU, the time-fused jnp
    sequence elsewhere (the Pallas interpreter is a correctness surrogate,
    not a fast path — running it per scan step would dominate the client
    step on CPU)."""
    if cfg.cell_path != "auto":
        return cfg.cell_path
    return "fused" if jax.default_backend() == "tpu" else "seq"


def init(key, cfg: ModelConfig):
    ke, kx, kh, kp = jax.random.split(key, 4)
    d, h = cfg.d_model, cfg.d_ff  # embedding dim, hidden size
    return {
        "embed": embedding_init(ke, cfg),
        # split gate matrices — fan-in matches the fused (d+h, 3h) matrix
        # they replace, so init statistics are unchanged by the layout
        "w_x": L.dense_init(kx, (d, 3 * h), in_dim=d + h),
        "w_h": L.dense_init(kh, (h, 3 * h), in_dim=d + h),
        "b_gates": jnp.zeros((3 * h,), jnp.float32),
        "w_proj": L.dense_init(kp, (h, d), in_dim=h),
    }


def _input_projection(params, x, cd):
    """Hoisted input half of the gate pre-activations for *all* timesteps:
    one (B·S, d) @ (d, 3h) GEMM + bias. x: (B, S, d) → zx (B, S, 3h) f32."""
    B, S, d = x.shape
    zx = (x.reshape(B * S, d) @ params["w_x"].astype(cd)).astype(jnp.float32)
    return zx.reshape(B, S, -1) + params["b_gates"]


def _recurrence(params, zx, cfg: ModelConfig, remat: bool):
    """Run the CIFG recurrence over zx (B, S, 3h) → hs (B, S, h) f32,
    dispatching on the resolved ``cell_path``."""
    B = zx.shape[0]
    hidden = cfg.d_ff
    h0 = jnp.zeros((B, hidden), jnp.float32)
    c0 = jnp.zeros((B, hidden), jnp.float32)
    path = resolve_cell_path(cfg)
    if path in ("fused", "seq"):
        hs, _ = cifg_sequence(zx.transpose(1, 0, 2), h0, c0,
                              params["w_h"], cell=path,
                              compute_dtype=cfg.compute_dtype, remat=remat)
        return hs.transpose(1, 0, 2)

    def step(carry, zx_t):
        h, c = cifg_cell_ref(zx_t, carry[0], carry[1], params["w_h"],
                             compute_dtype=cfg.compute_dtype)
        return (h, c), h

    if remat:
        step = jax.checkpoint(step)
    _, hs = jax.lax.scan(step, (h0, c0), zx.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def _features(params, batch, cfg: ModelConfig, remat: bool):
    """Tokens → the head's input ``y`` (B, S, d) in the compute dtype."""
    cd = jnp.dtype(cfg.compute_dtype)
    with scope("embed"):
        if resolve_cell_path(cfg) == "fused":
            x = embed_lookup(params["embed"]["tok"], batch["tokens"], cd)
        else:
            x = embed_tokens(params["embed"], batch["tokens"], cd)  # (B,S,d)
    zx = _input_projection(params, x, cd)          # (B,S,3h) — one GEMM
    hs = _recurrence(params, zx, cfg, remat)
    hs = hs.astype(cd)                             # (B,S,hidden)
    return hs @ params["w_proj"].astype(cd)        # (B,S,d)


def forward(params, batch, cfg: ModelConfig, *, remat: bool = False):
    y = _features(params, batch, cfg, remat)
    with scope("head"):
        return lm_logits(params["embed"], y)


def loss_fn(params, batch, cfg: ModelConfig, *, remat: bool = False):
    y = _features(params, batch, cfg, remat)
    with scope("head"):
        if resolve_cell_path(cfg) == "fused":
            w = params["embed"].get("head", params["embed"]["tok"])
            return lm_head_xent(y, w, batch["labels"], batch.get("mask"),
                                vocab=cfg.vocab)
        return L.lm_loss(lm_logits(params["embed"], y), batch["labels"],
                         cfg.vocab, batch.get("mask"))


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int):
    """Decode cache. Every leaf is *per-row* (leading dim = batch): the
    serving engine scatters/gathers individual sessions by slot index, so
    nothing in the cache may be shared across rows (`repro.serve.engine`
    validates this contract)."""
    h = cfg.d_ff
    return {"h": jnp.zeros((batch_size, h), jnp.float32),
            "c": jnp.zeros((batch_size, h), jnp.float32),
            "pos": jnp.zeros((batch_size,), jnp.int32)}


# XLA compiles a one-row matmul on the TPU differently from a multi-row
# one, so a session's logits and state would depend on how many sessions
# share its batch: a 1-row reference decode and a 16-slot engine tick differ
# in the last bits. Serving batches are therefore padded to the 8-row
# sublane tile, the chip's own row granularity, so that every row's
# arithmetic is the same whatever the batch (the serving parity contract of
# `repro.serve`).
SERVE_ROWS = 8


def _pad_rows(a, rows: int, value=0):
    return jnp.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1),
                   constant_values=value)


def _head(params, h, cd):
    """Final state (B, h) → next-token logits (B, Vpad) f32."""
    y = (h.astype(cd) @ params["w_proj"].astype(cd))[:, None, :]
    return lm_logits(params["embed"], y)[:, 0, :]


def prefill(params, batch, cfg: ModelConfig, *, max_len: int = None):
    """Prompt prefill → (last-position logits (B, V), decode cache).

    An optional ``batch["length"]`` ((B,) int32, 1 ≤ length ≤ S) marks each
    row's true prompt length inside right-padded ``tokens`` — the serving
    engine's bucket-padded admission path; without it every row is ``S``
    long. The recurrence is causal and the hoisted input-projection GEMM is
    row-stable over row-tile multiples, so the state and logits gathered at
    ``length - 1`` are bit-identical to an unpadded prefill of exactly
    ``length`` tokens (tests/test_serve_engine.py pins this)."""
    del max_len  # recurrent state — nothing to pad
    cd = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    B, S = tokens.shape
    length = (jnp.asarray(batch["length"], jnp.int32) if "length" in batch
              else jnp.full((B,), S, jnp.int32))
    rows = -(-B // SERVE_ROWS) * SERVE_ROWS
    x = embed_tokens(params["embed"], _pad_rows(tokens, rows), cd)
    zx = _input_projection(params, x, cd)
    h0 = jnp.zeros((rows, cfg.d_ff), jnp.float32)
    # full (S, rows, H) state stacks through the same per-step forward as
    # _recurrence ("seq"'s step IS the "ref" cell), gathered at length-1
    path = resolve_cell_path(cfg)
    hs, cs = cifg_states(zx.transpose(1, 0, 2), h0, h0, params["w_h"],
                         cell="fused" if path == "fused" else "seq",
                         compute_dtype=cfg.compute_dtype)
    last = _pad_rows(length, rows, S) - 1
    h = hs[last, jnp.arange(rows)]
    c = cs[last, jnp.arange(rows)]
    logits = _head(params, h, cd)
    return logits[:B], {"h": h[:B], "c": c[:B], "pos": length}


def decode_step(params, tokens, cache, cfg: ModelConfig):
    cd = jnp.dtype(cfg.compute_dtype)
    B = tokens.shape[0]
    rows = -(-B // SERVE_ROWS) * SERVE_ROWS
    h, c = _pad_rows(cache["h"], rows), _pad_rows(cache["c"], rows)
    x = embed_tokens(params["embed"], _pad_rows(tokens, rows), cd)
    zx = (x @ params["w_x"].astype(cd)).astype(jnp.float32) \
        + params["b_gates"]
    if resolve_cell_path(cfg) == "fused":
        h, c = cifg_step(zx, h, c, params["w_h"],
                         compute_dtype=cfg.compute_dtype)
    else:
        h, c = cifg_cell_ref(zx, h, c, params["w_h"],
                             compute_dtype=cfg.compute_dtype)
    logits = _head(params, h, cd)
    return logits[:B], {"h": h[:B], "c": c[:B], "pos": cache["pos"] + 1}


def build(cfg: ModelConfig) -> Model:
    if cfg.cell_path not in CELL_PATHS:
        raise ValueError(f"cell_path must be one of {CELL_PATHS}, "
                         f"got {cfg.cell_path!r}")
    return Model(
        cfg=cfg,
        init=partial(init, cfg=cfg),
        forward=partial(forward, cfg=cfg),
        loss_fn=partial(loss_fn, cfg=cfg),
        init_cache=partial(init_cache, cfg),
        prefill=partial(prefill, cfg=cfg),
        decode_step=partial(decode_step, cfg=cfg),
    )

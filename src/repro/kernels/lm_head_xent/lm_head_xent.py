"""Pallas TPU kernels for the client step's output head: the tied-embedding
logits, the softmax cross-entropy and its backward, without the logits.

Written as XLA ops, the head writes a (rows, Vpad) f32 logits tensor to
HBM and reads it back three times: for ``lse`` and the label pick, and once
for each backward matmul. At the paper's vocabulary (10,240 padded
columns) that traffic is most of the head's time. These kernels keep
a block of rows and the whole table in VMEM and sweep the vocabulary in
``block_v`` column chunks:

- forward: ``z = y @ w_chunkᵀ`` (f32 accumulation), an online max and
  exp-sum per row, and the label's logit; only ``lse`` and the picked logit
  (one f32 per row each) leave the kernel;
- backward: recompute ``z``, form ``g = ct · (exp(z − lse) − onehot)`` in
  f32, add ``gᵀ @ y`` into the chunk's rows of ``dw`` and ``g @ w_chunk``
  into ``dy``.

Columns at or past ``vocab`` (the table's padding) are masked to
``NEG_INF`` in the forward and get a zero gradient, as in
`repro.models.layers.lm_loss`. The table arrives in its parameter dtype and
each chunk is cast to the rows' compute dtype inside the kernel, so no
rounded copy of the table is written to HBM.

Layout: ``y (R, d)``, ``w (V, d)`` with ``V`` a multiple of ``block_v``
(itself a multiple of 128), per-row vectors as ``(R, 1)`` columns. The grid
runs over blocks of ``row_block`` rows (a multiple of `ROW_TILE` dividing
``R``); the table and ``dw`` stay resident across it. One client's batch
(800 rows) is one block, and under ``vmap`` (the engine's client chunk)
Pallas's batching rule adds the client axis to the grid, one client's
operands per step. `ops.lm_head_xent` is the supported padding path; ragged
shapes fail loudly here.

``interpret=None`` (default) auto-selects per backend: compiled Pallas on
TPU, the Pallas interpreter elsewhere, as in `kernels.cifg_cell`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 16        # rows padded to the bf16 sublane tile
ROW_BLOCK = 1024     # most rows per grid step; one client's 800 fit
BLOCK_V = 2048       # widest vocabulary chunk per inner step
NEG_INF = -1e30      # the masked-column logit of `layers.lm_loss`
VMEM_LIMIT = 64 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))   # a @ bᵀ: contract the minor dims
_TN = (((0,), (0,)), ((), ()))   # aᵀ @ b: contract the row dims


def default_interpret() -> bool:
    """Backend auto-select: real Pallas on TPU, interpreter elsewhere."""
    return jax.default_backend() != "tpu"


def _check(name: str, y, w, row_block: int, block_v: int, *rows) -> None:
    R, d = y.shape if y.ndim == 2 else (0, 0)
    ok = (y.ndim == 2 and w.ndim == 2 and w.shape[1] == d
          and row_block % ROW_TILE == 0 and R % row_block == 0
          and block_v % LANES == 0 and w.shape[0] % block_v == 0
          and all(r.shape == (R, 1) for r in rows))
    if not ok:
        raise ValueError(
            f"{name}: operands must be y (R, d), w (V, d) and per-row "
            f"(R, 1) columns, R a multiple of row_block ({row_block}, itself "
            f"a multiple of {ROW_TILE}) and V of block_v ({block_v}, itself "
            f"a multiple of {LANES}); see repro.kernels.lm_head_xent.ops for "
            f"the padding path — got y {tuple(y.shape)}, w "
            f"{tuple(w.shape)}, rows {[tuple(r.shape) for r in rows]}")


def _logits(y, w):
    """A chunk's logits ``y @ wᵀ`` in f32."""
    return jax.lax.dot_general(y, w, _NT, preferred_element_type=jnp.float32)


def _sweep(vocab: int, block_v: int, n_chunks: int, body, carry):
    """Run ``body(start, masked, carry)`` over the column chunks: a loop
    over those wholly below ``vocab``, then the rest unrolled with the
    padding mask on."""
    full = min(vocab // block_v, n_chunks)

    def step(k, c):
        return body(pl.multiple_of(k * block_v, block_v), False, c)

    carry = jax.lax.fori_loop(0, full, step, carry)
    for k in range(full, n_chunks):
        carry = body(k * block_v, True, carry)
    return carry


def _chunk(y, w_ref, start, block_v):
    """The table chunk at ``start`` in ``y``'s dtype, its logits and their
    column ids (1, block_v)."""
    w = w_ref[pl.ds(start, block_v), :].astype(y.dtype)
    cols = start + jax.lax.broadcasted_iota(jnp.int32, (1, block_v), 1)
    return w, _logits(y, w), cols


def _fwd_kernel(y_ref, w_ref, lab_ref, lse_ref, pick_ref, *, vocab, block_v):
    y, lab = y_ref[...], lab_ref[...]

    def body(start, masked, carry):
        m, s, t = carry
        _, z, cols = _chunk(y, w_ref, start, block_v)
        if masked:
            z = jnp.where(cols < vocab, z, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(z, axis=1, keepdims=True))
        s = s * jnp.exp(m - m_new) + jnp.sum(jnp.exp(z - m_new), axis=1,
                                             keepdims=True)
        t = t + jnp.sum(jnp.where(cols == lab, z, 0.0), axis=1,
                        keepdims=True)
        return m_new, s, t

    col = jnp.zeros((y.shape[0], 1), jnp.float32)
    m, s, t = _sweep(vocab, block_v, w_ref.shape[0] // block_v, body,
                     (col - jnp.inf, col, col))
    lse_ref[...] = m + jnp.log(s)
    pick_ref[...] = t


def _specs(row_block: int, d: int, V: int, n_rows: int):
    """Block specs: a row block of ``y``, the whole table, and ``n_rows``
    per-row (R, 1) columns."""
    return ([pl.BlockSpec((row_block, d), lambda i: (i, 0)),
             pl.BlockSpec((V, d), lambda i: (0, 0))]
            + [pl.BlockSpec((row_block, 1), lambda i: (i, 0))] * n_rows)


def xent_fwd(y, w, labels, *, vocab: int, row_block: int,
             block_v: int = BLOCK_V, interpret=None):
    """Per-row ``(lse, picked logit)``, each (R, 1) f32, of the logits
    ``y @ wᵀ`` over the first ``vocab`` columns."""
    _check("xent_fwd", y, w, row_block, block_v, labels)
    if interpret is None:
        interpret = default_interpret()
    (R, d), V = y.shape, w.shape[0]
    col = jax.ShapeDtypeStruct((R, 1), jnp.float32)
    row = pl.BlockSpec((row_block, 1), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, vocab=vocab, block_v=block_v),
        grid=(R // row_block,),
        in_specs=_specs(row_block, d, V, 1),
        out_specs=(row, row),
        out_shape=(col, col),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(y, w, labels)


def _bwd_kernel(y_ref, w_ref, lab_ref, lse_ref, ct_ref, dy_ref, dw_ref, *,
                vocab, block_v):
    y, lab, lse, ct = y_ref[...], lab_ref[...], lse_ref[...], ct_ref[...]
    dy_ref[...] = jnp.zeros(dy_ref.shape, jnp.float32)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    def body(start, masked, carry):
        w, z, cols = _chunk(y, w_ref, start, block_v)
        g = ct * jnp.exp(z - lse) - jnp.where(cols == lab, ct, 0.0)
        if masked:
            g = jnp.where(cols < vocab, g, 0.0)
        g = g.astype(y.dtype)
        dw_ref[pl.ds(start, block_v), :] += jax.lax.dot_general(
            g, y, _TN, preferred_element_type=jnp.float32)
        dy_ref[...] += jnp.dot(g, w, preferred_element_type=jnp.float32)
        return carry

    _sweep(vocab, block_v, w_ref.shape[0] // block_v, body, 0)


def xent_bwd(y, w, labels, lse, ct, *, vocab: int, row_block: int,
             block_v: int = BLOCK_V, interpret=None):
    """Gradients of ``Σ_r ct_r · (lse_r − picked_r)``: ``(dy (R, d),
    dw (V, d))`` in f32; padding columns get zero."""
    _check("xent_bwd", y, w, row_block, block_v, labels, lse, ct)
    if interpret is None:
        interpret = default_interpret()
    (R, d), V = y.shape, w.shape[0]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, vocab=vocab, block_v=block_v),
        grid=(R // row_block,),
        in_specs=_specs(row_block, d, V, 3),
        out_specs=(pl.BlockSpec((row_block, d), lambda i: (i, 0)),
                   pl.BlockSpec((V, d), lambda i: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct(y.shape, jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(y, w, labels, lse, ct)

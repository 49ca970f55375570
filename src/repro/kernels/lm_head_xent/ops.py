"""jit-friendly wrapper: the tied-embedding head's cross-entropy with a
custom VJP, on the fused kernels of `lm_head_xent`.

``lm_head_xent(y, w, labels, mask, vocab=V)`` is the loss of
``layers.lm_loss(embed.lm_logits({"tok": w}, y), labels, V, mask)`` (the
reference `ref.lm_head_xent_ref`) without the logits: the forward kernel
returns each row's ``lse`` and picked logit, the masked mean over rows is
plain jnp, and its per-row cotangent drives the backward kernel through
``jax.custom_vjp``. Rows are flattened and padded to the kernel's row tile
(padded rows get a zero cotangent) and blocked in at most `ROW_BLOCK`
rows, and the table is swept in the widest chunk of at most `BLOCK_V`
columns that divides it (padded to 128 rows, masked like the vocabulary
padding).

Precision follows the XLA path it replaces: the logits accumulate in f32
from operands in ``y``'s dtype (the model's compute dtype), the softmax and
``lse`` are f32, and the backward matmuls take the f32 ``g`` rounded to
that dtype, as the TPU's default-precision dot does with the f32
cotangent. ``dy`` is returned in ``y``'s dtype and ``dw`` in f32.

``interpret=None`` auto-selects per backend (compiled Pallas on TPU, the
Pallas interpreter elsewhere); the op and its VJP batch under ``vmap``
with ``w`` batched or not (the engine's client chunk makes the table
per-client after the first local step).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.lm_head_xent import lm_head_xent as K


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad_rows(a, rows: int):
    return jnp.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _blocks(R: int, V: int):
    """``(row_block, padded rows, column chunk, padded table rows)``: rows
    in the fewest blocks of at most `ROW_BLOCK`, and the widest chunk of
    at most `BLOCK_V` that divides the table padded to 128 rows."""
    n = -(-R // K.ROW_BLOCK)
    row_block = _round_up(-(-R // n), K.ROW_TILE)
    Vp = _round_up(V, K.LANES)
    chunk = max(b for b in range(K.LANES, min(K.BLOCK_V, Vp) + 1, K.LANES)
                if Vp % b == 0)
    return row_block, n * row_block, chunk, Vp


def _kernel_args(y, w, labels):
    """The kernels' padded operands and static block sizes."""
    row_block, Rp, chunk, Vp = _blocks(y.shape[0], w.shape[0])
    lab = _pad_rows(labels.astype(jnp.int32)[:, None], Rp)
    return (_pad_rows(y, Rp), _pad_rows(w, Vp), lab,
            dict(row_block=row_block, block_v=chunk))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _row_nll(y, w, labels, vocab, interpret):
    return _row_nll_fwd(y, w, labels, vocab, interpret)[0]


def _row_nll_fwd(y, w, labels, vocab, interpret):
    yp, wp, lab, blocks = _kernel_args(y, w, labels)
    lse, pick = K.xent_fwd(yp, wp, lab, vocab=vocab, interpret=interpret,
                           **blocks)
    nll = (lse - pick)[: y.shape[0], 0]
    return nll, (y, w, labels, lse)


def _row_nll_bwd(vocab, interpret, res, ct):
    y, w, labels, lse = res
    yp, wp, lab, blocks = _kernel_args(y, w, labels)
    ctp = _pad_rows(ct.astype(jnp.float32)[:, None], yp.shape[0])
    dy, dw = K.xent_bwd(yp, wp, lab, lse, ctp, vocab=vocab,
                        interpret=interpret, **blocks)
    return (dy[: y.shape[0]].astype(y.dtype),
            dw[: w.shape[0]].astype(w.dtype), None)


_row_nll.defvjp(_row_nll_fwd, _row_nll_bwd)


def lm_head_xent(y, w, labels, mask=None, *, vocab: int, interpret=None):
    """Mean next-token cross-entropy of the logits ``y @ wᵀ`` over the first
    ``vocab`` columns, never materialising them.

    y: (..., d) features in the compute dtype; w: (Vpad, d) tied table in
    its parameter dtype; labels: (...) int; mask: (...) row weights or
    None. Returns the f32 scalar ``Σ mask·nll / max(Σ mask, 1)`` (the plain
    mean without a mask), as `repro.models.layers.lm_loss` does.
    """
    if y.shape[:-1] != labels.shape or w.ndim != 2 \
            or w.shape[1] != y.shape[-1] \
            or (mask is not None and mask.shape != labels.shape):
        raise ValueError(
            f"lm_head_xent: expected y (..., d), w (Vpad, d), labels and "
            f"mask (...) — got y {tuple(y.shape)}, w {tuple(w.shape)}, "
            f"labels {tuple(labels.shape)}, mask "
            f"{None if mask is None else tuple(mask.shape)}")
    if interpret is None:
        interpret = K.default_interpret()
    nll = _row_nll(y.reshape(-1, y.shape[-1]), w, labels.reshape(-1),
                   int(vocab), bool(interpret))
    if mask is None:
        return jnp.mean(nll)
    m = mask.reshape(-1).astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)

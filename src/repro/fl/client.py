"""UserUpdate(k, θ) — Algorithm 1's client procedure + cohort accumulation.

E local epochs of minibatch SGD at learning rate η_c, then the model delta
Δ = θ_local − θ0 clipped to L2 norm S. Pure-JAX, jit-compiled once per
(model, batch-shape).

Two cohort-level consumers share this file:

* :func:`round_compute` — the host reference round body, and
* the simulation engine (`repro.fl.engine`), which calls
  :func:`stream_block_sums` per cohort shard.

Both accumulate the round's clipped sum **streamingly**: a ``lax.scan`` over
contiguous cohort *chunks* (``cohort_chunk`` clients vmapped per step) runs
local SGD per chunk and folds each chunk's clipped updates straight into the
canonical block partials (`repro.fl.reduction`), so peak update memory is
O(cohort_chunk · |params|) instead of the materializing O(cohort · |params|)
stack. The per-slot fold is strictly sequential (``reduction.slot_fold``
association), which makes trajectories bit-identical across every
``cohort_chunk`` dividing the canonical block size — the same
topology-invariance contract the cross-shard block tree provides one level
up. ``cohort_chunk=0`` selects the legacy materializing path (kept as the
validated reference and the benchmark baseline).

The per-slot clip→accumulate goes through
`core.clipping.clip_accumulate_tree`: the fused Pallas ``dp_clip`` kernels
by default (``clip_path="fused"``; interpret mode on CPU, compiled on TPU),
or the pytree reference (``clip_path="tree"``).
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ClientConfig, DPConfig
from repro.core.clipping import clip_accumulate_tree, clip_by_global_norm
from repro.fl.reduction import (CANON_BLOCKS, canon_pad, fold_blocks,
                                resolve_chunk)
from repro.models.api import Model
from repro.utils.pytree import tree_sub, tree_zeros_like
from repro.utils.spans import scope


def local_sgd(model: Model, params, batches: Dict[str, jnp.ndarray],
              client: ClientConfig):
    """batches: pytree of (n_batches, B, ...) arrays. Runs E epochs of SGD."""

    def sgd_batch(p, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(p, batch)
        new_p = jax.tree_util.tree_map(
            lambda w, g: (w.astype(jnp.float32)
                          - client.lr * g.astype(jnp.float32)).astype(w.dtype),
            p, grads)
        return new_p, loss

    def epoch(p, _):
        p, losses = jax.lax.scan(sgd_batch, p, batches)
        return p, jnp.mean(losses)

    params, losses = jax.lax.scan(epoch, params, None,
                                  length=client.local_epochs)
    return params, jnp.mean(losses)


def local_delta(model: Model, params0, batches, client: ClientConfig):
    """Unclipped client delta: E local epochs, then Δ = θ_local − θ0 in f32.
    Returns (delta pytree, mean loss)."""
    params_local, loss = local_sgd(model, params0, batches, client)
    delta = tree_sub(
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), params_local),
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), params0))
    return delta, loss


def local_deltas(model: Model, params, stacked_batches, client: ClientConfig):
    """:func:`local_delta` vmapped over a stacked client chunk — the
    *compute* half of the streaming accumulator: the (chunk, |params|) delta
    stack is the only per-client buffer that ever materializes."""
    return jax.vmap(lambda b: local_delta(model, params, b, client))(
        stacked_batches)


def user_update(model: Model, params0, batches, client: ClientConfig,
                dp: DPConfig):
    """Returns (clipped Δ_k, pre-clip norm, was_clipped, mean loss)."""
    delta, loss = local_delta(model, params0, batches, client)
    clipped, norm, was_clipped = clip_by_global_norm(delta, dp.clip_norm)
    return clipped, norm, was_clipped, loss


def client_updates(model: Model, params, stacked_batches,
                   client: ClientConfig, dp: DPConfig):
    """Per-client :func:`user_update` vmapped over the stacked cohort —
    *unreduced*: (clipped Δ stack (C, …), norms (C,), was_clipped (C,),
    losses (C,)). This is the materializing path (O(cohort) update memory),
    kept as the validated reference; the streaming accumulator
    (:func:`stream_block_sums`) replaces it on the hot path."""
    def one(batches):
        return user_update(model, params, batches, client, dp)

    return jax.vmap(one)(stacked_batches)


# ------------------------------------------------------- streaming fold


def chunk_accumulate(acc, deltas, losses, mask, clip_norm: float, *,
                     clip_path: str = "fused", interpret=None,
                     guard_nonfinite: bool = False):
    """Fold one chunk's unclipped client deltas into the running block
    accumulator, one slot at a time.

    ``acc`` is ``(update_acc pytree f32, stats_acc (4,) f32)`` where the
    stats pack [Σ norms, Σ clipped-flags, Σ losses, Σ mask]. ``deltas`` has
    a leading (chunk,) axis, ``mask`` is the chunk's 0/1 slot mask folded
    into the clip factor (masked slots contribute exactly ±0). The fold is a
    strict left-to-right ``lax.scan`` — the canonical intra-block
    association (`reduction.slot_fold`), so splitting a block into chunks of
    any dividing size reproduces bit-identical partials.

    ``guard_nonfinite`` is the server-side corrupt-report rejection of the
    production fault model (`fl.faults`): a slot whose delta (or loss)
    carries any non-finite value is rejected *before* it can poison the
    accumulator — its mask is zeroed, so it contributes exact ±0 to both
    the clipped sum and the stat sums, exactly like a dropped/Poisson-
    excluded slot, and ``stats[3]`` ends up counting only *accepted*
    reports (the count the round's report goal is checked against)."""
    m = mask.astype(jnp.float32)

    def fold(carry, slot):
        upd, stats = carry
        delta, loss, mi = slot
        if guard_nonfinite:
            leaves = jax.tree_util.tree_leaves(delta)
            ok = jnp.all(jnp.stack(
                [jnp.all(jnp.isfinite(l)) for l in leaves]
                + [jnp.isfinite(loss)])).astype(jnp.float32)
            # zero the garbage values too: NaN·0 = NaN, so a zeroed mask
            # alone would still poison the norm/accumulator arithmetic
            delta = jax.tree_util.tree_map(
                lambda l: jnp.where(jnp.isfinite(l), l, 0.0), delta)
            loss = jnp.where(jnp.isfinite(loss), loss, 0.0)
            mi = mi * ok
        upd, norm, flag = clip_accumulate_tree(
            upd, delta, clip_norm, scale=mi, clip_path=clip_path,
            interpret=interpret)
        stats = stats + jnp.stack([norm * mi, flag * mi, loss * mi, mi])
        return (upd, stats), None

    with scope("clip_fold"):
        (upd, stats), _ = jax.lax.scan(fold, acc, (deltas, losses, m))
    return upd, stats


def stream_block_sums(compute_chunk, chunk_inputs, chunk_masks, params_like,
                      clip_norm: float, *, clip_path: str = "fused",
                      interpret=None, guard_nonfinite: bool = False):
    """Streaming chunked accumulation of one cohort slice's canonical block
    partials — the engine's and the host loop's shared round-sum core.

    ``chunk_inputs`` is a pytree whose leaves carry leading axes
    ``(n_blocks, chunks_per_block, chunk, ...)`` (contiguous slots, so chunk
    boundaries nest inside block boundaries); ``chunk_masks`` is the
    matching ``(n_blocks, chunks_per_block, chunk)`` 0/1 slot mask.
    ``compute_chunk(inputs_slice) -> (delta stack (chunk, …) f32, losses
    (chunk,))`` produces one chunk's unclipped client deltas (gather + local
    SGD); each chunk is then clipped and folded into the block's running
    partial by :func:`chunk_accumulate`. A fully-masked chunk (padding past
    the realized round) skips its compute entirely via a scalar
    ``lax.cond`` — and because masked slots would have contributed exactly
    ±0, skipping is bit-identical to computing.

    Returns ``(block partial pytree with leading (n_blocks,) axis,
    (n_blocks, 4) stat partials)`` — the same contract the materializing
    block-sum path feeds into the pairwise `reduction.fold_blocks` tree.
    Peak live update memory: one accumulator + one (chunk, |params|) stack.

    ``guard_nonfinite`` threads the corrupt-report rejection into the
    per-slot fold (see :func:`chunk_accumulate`) — the engine enables it
    exactly when a `fl.faults.FaultConfig` injects non-finite updates.
    """
    zero = (tree_zeros_like(params_like, jnp.float32),
            jnp.zeros((4,), jnp.float32))
    chunk = chunk_masks.shape[-1]
    if chunk == 1 and chunk_masks.shape[1] > 1:
        # XLA simplifies away a degenerate (size-1) vmap batch dimension,
        # which changes the per-client arithmetic bitwise vs any width ≥ 2.
        # Chunk sizes ≥ 2 are prefix-consistent with each other, so pad the
        # width-1 compute with a duplicate slot and discard the copy — this
        # keeps cohort_chunk=1 inside the bit-parity family. When the block
        # size itself is 1 (chunks_per_block == 1) the dividing-chunk family
        # is the singleton {1} and every shard count runs the same width-1
        # program, so the doubled compute would buy no parity — skip it.
        inner = compute_chunk

        def compute_chunk(inputs):   # noqa: F811 — widened wrapper
            two = jax.tree_util.tree_map(
                lambda l: jnp.concatenate([l, l], axis=0), inputs)
            deltas, losses = inner(two)
            return (jax.tree_util.tree_map(lambda l: l[:1], deltas),
                    losses[:1])

    def chunk_step(acc, cinp):
        inputs, cmask = cinp

        def live(a):
            deltas, losses = compute_chunk(inputs)
            return chunk_accumulate(a, deltas, losses, cmask, clip_norm,
                                    clip_path=clip_path, interpret=interpret,
                                    guard_nonfinite=guard_nonfinite)

        return jax.lax.cond(jnp.any(cmask > 0), live, lambda a: a, acc), None

    def block_step(_, binp):
        acc, _ = jax.lax.scan(chunk_step, zero, binp)
        return None, acc

    _, (partials, stats) = jax.lax.scan(block_step, None,
                                        (chunk_inputs, chunk_masks))
    return partials, stats


# ------------------------------------------------------- host round body


def round_compute(model: Model, params, stacked_batches,
                  client: ClientConfig, dp: DPConfig, mask=None, *,
                  cohort_chunk=None, clip_path: str = "fused",
                  interpret=None):
    """Pure round body: (params, stacked client batches (C, nb, B, S)) →
    (sum of clipped updates, mean norm, frac clipped, mean loss).

    ``mask`` (optional (C,) 0/1) folds per-slot participation into the
    weighted sum — Poisson-sampled variable-size rounds keep a fixed-shape
    cohort buffer and zero out the unselected slots here, so the clipped sum
    and the per-round stats only see the clients that actually participated.

    The accumulation is the *same* canonical streaming path as the engine's
    (:func:`stream_block_sums` over the block grid of `repro.fl.reduction`):
    the cohort pads to the canonical block grid (pad slots alias slot 0's
    batches under a zero mask, so their contribution is exactly ±0) and each
    block folds ``cohort_chunk`` clients at a time — identical association,
    so given identical batches the host sum is bit-equal to the engine's.
    ``cohort_chunk=None`` auto-sizes per block; ``0`` restores the legacy
    materializing path (O(C) update memory, XLA-reduction association).

    Traceable — :func:`make_round_fn` wraps it in jit for the per-round host
    loop.
    """
    C = jax.tree_util.tree_leaves(stacked_batches)[0].shape[0]
    padded = canon_pad(C)
    blk = padded // CANON_BLOCKS
    chunk = resolve_chunk(cohort_chunk, blk, strict=False)
    if chunk == 0:
        return _round_compute_materialized(model, params, stacked_batches,
                                           client, dp, mask)
    m = (jnp.ones((C,), jnp.float32) if mask is None
         else mask.astype(jnp.float32))
    pad = padded - C
    if pad:
        stacked_batches = jax.tree_util.tree_map(
            lambda l: jnp.concatenate(
                [l, jnp.broadcast_to(l[:1], (pad,) + l.shape[1:])], axis=0),
            stacked_batches)
        m = jnp.concatenate([m, jnp.zeros((pad,), jnp.float32)])
    cpb = blk // chunk
    binp = jax.tree_util.tree_map(
        lambda l: l.reshape((CANON_BLOCKS, cpb, chunk) + l.shape[1:]),
        stacked_batches)
    partials, stats = stream_block_sums(
        lambda b: local_deltas(model, params, b, client),
        binp, m.reshape(CANON_BLOCKS, cpb, chunk), params, dp.clip_norm,
        clip_path=clip_path, interpret=interpret)
    total = jax.tree_util.tree_map(fold_blocks, partials)
    s = fold_blocks(stats)
    denom = jnp.maximum(s[3], 1.0)
    return total, s[0] / denom, s[1] / denom, s[2] / denom


def _round_compute_materialized(model: Model, params, stacked_batches,
                                client: ClientConfig, dp: DPConfig,
                                mask=None):
    """Legacy materializing round body (``cohort_chunk=0``): vmap the whole
    cohort, stack every clipped update, reduce once. O(C · |params|) peak
    memory — kept as the streaming path's validated reference and the
    benchmark baseline."""
    clipped, norms, flags, losses = client_updates(model, params,
                                                   stacked_batches, client, dp)
    if mask is None:
        total = jax.tree_util.tree_map(lambda l: jnp.sum(l, axis=0), clipped)
        return total, jnp.mean(norms), jnp.mean(flags), jnp.mean(losses)
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(m), 1.0)
    total = jax.tree_util.tree_map(
        lambda l: jnp.tensordot(m, l.astype(jnp.float32), axes=1), clipped)
    return (total, jnp.sum(norms * m) / denom, jnp.sum(flags * m) / denom,
            jnp.sum(losses * m) / denom)


def make_round_fn(model: Model, client: ClientConfig, dp: DPConfig,
                  cohort_chunk=None, clip_path: str = "fused"):
    """jit-compiled :func:`round_compute` for the host-loop trainer. The
    chunk size re-resolves per traced cohort shape (the host loop's realized
    round size varies), so a fluctuating check-in pool still streams."""

    @partial(jax.jit, static_argnums=())
    def round_fn(params, stacked_batches):
        return round_compute(model, params, stacked_batches, client, dp,
                             cohort_chunk=cohort_chunk, clip_path=clip_path)

    return round_fn

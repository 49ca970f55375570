"""Pallas TPU flash attention (causal / sliding-window / bidirectional).

Online-softmax tiling: the grid is (B, H, nQ, nK) with the KV dimension
innermost and sequential; running max `m`, normalizer `l`, and the output
accumulator live in VMEM scratch across KV steps. Block shapes are
(BLOCK_Q × head_dim) / (BLOCK_K × head_dim) with the MXU-aligned 128 lane
dimension; softmax statistics are carried broadcast across lanes.

The sliding-window mask is what lets the dense/MoE/VLM/audio architectures
run the ``long_500k`` decode shape sub-quadratically (DESIGN.md §4).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, causal: bool, window: int, scale: float, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (BQ, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (BK, hd)
    v = v_ref[0, 0].astype(jnp.float32)            # (BK, hd)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (BQ, BK)

    q_idx = iq * BLOCK_Q + jax.lax.broadcasted_iota(jnp.int32,
                                                    scores.shape, 0)
    k_idx = ik * BLOCK_K + jax.lax.broadcasted_iota(jnp.int32,
                                                    scores.shape, 1)
    mask = k_idx < seq_k
    if causal:
        mask &= k_idx <= q_idx
    if window > 0:
        mask &= k_idx > q_idx - window
    scores = jnp.where(mask, scores, NEG_INF)

    m_prev = m_ref[...][:, :1]                      # (BQ, 1)
    m_cur = jnp.max(scores, axis=1, keepdims=True)  # (BQ, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                 # (BQ, 1)
    p = jnp.exp(scores - m_new)                     # (BQ, BK)
    l_new = l_ref[...][:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == pl.num_programs(3) - 1)
    def _done():
        l = l_ref[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         seq_k: int = None, interpret: bool = True):
    """q: (B, H, Sq, hd); k, v: (B, H, Sk, hd) (kv heads pre-broadcast).
    Sq/Sk padded to BLOCK multiples by the ops wrapper; ``seq_k`` is the
    TRUE (pre-padding) KV length — padded slots are masked."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    grid = (B, H, Sq // BLOCK_Q, Sk // BLOCK_K)
    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window,
        scale=1.0 / math.sqrt(hd), seq_k=seq_k if seq_k is not None else Sk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, BLOCK_Q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, BLOCK_K, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK_Q, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BLOCK_Q, 128), jnp.float32),
            pltpu.VMEM((BLOCK_Q, 128), jnp.float32),
            pltpu.VMEM((BLOCK_Q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)

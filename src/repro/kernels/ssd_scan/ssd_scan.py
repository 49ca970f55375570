"""Pallas TPU kernel for the Mamba-2 chunked SSD scan [arXiv:2405.21060].

TPU adaptation of the paper's "state-space duality": within a chunk of Q
tokens the recurrence is evaluated in its dual quadratic (attention-like)
form — three (Q×Q)/(Q×N)/(Q×p) matmuls that run on the MXU — while a
(p × N) state carried in VMEM scratch propagates the recurrence across
chunks. Grid (B, H, n_chunks), chunk dim innermost/sequential.

This replaces the GPU implementation's warp-level chunk scan: on TPU the
inter-chunk dependency is expressed through scratch persistence across the
sequential grid dimension instead of shared-memory accumulators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, state_out_ref,
                state_ref):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)      # (Q, p)
    dt = dt_ref[0, :, 0].astype(jnp.float32)       # (Q,)
    Bm = b_ref[0].astype(jnp.float32)              # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)              # (Q, N)
    A = a_ref[0]                                   # scalar (negative)

    Q = x.shape[0]
    a = dt * A                                     # (Q,)
    cum = jnp.cumsum(a)                            # (Q,)
    # intra-chunk dual (quadratic) form
    seg = cum[:, None] - cum[None, :]              # (Q, Q)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    Lmat = jnp.where(tri, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    w = scores * Lmat * dt[None, :]
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk: contribution of the carried state
    state = state_ref[...]                         # (p, N)
    y_inter = jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, state, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # (Q, p)
    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update
    decay_out = jnp.exp(cum[-1] - cum)             # (Q,)
    dB = (dt * decay_out)[:, None] * Bm            # (Q, N)
    state_ref[...] = jnp.exp(cum[-1]) * state + jax.lax.dot_general(
        x, dB, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ic == pl.num_programs(2) - 1)
    def _done():
        state_out_ref[0, 0] = state_ref[...]


def ssd_scan_kernel(x, dt, Bm, Cm, A, *, interpret: bool = True):
    """x: (B,S,H,p); dt: (B,S,H) f32; Bm,Cm: (B,S,N); A: (H,) f32 (negative).
    S must be a multiple of CHUNK. Returns (y (B,S,H,p) f32,
    final_state (B,H,p,N) f32)."""
    Bsz, S, H, p = x.shape
    N = Bm.shape[-1]
    assert S % CHUNK == 0, (S, CHUNK)
    grid = (Bsz, H, S // CHUNK)
    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, CHUNK, 1, p), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, CHUNK, 1), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((1, CHUNK, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, CHUNK, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, h, c: (h,), memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, CHUNK, 1, p), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, 1, p, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bsz, S, H, p), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, H, p, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bm, Cm, A)

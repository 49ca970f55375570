"""Pallas TPU kernel for the input embedding's gradient: the table rows'
sums of the token rows' cotangents, as a one-hot matmul.

Written as XLA ops, the gradient of ``jnp.take(table, tokens)`` is a
scatter-add of every token row's cotangent into a zero table. On the TPU
that scatter runs row by row, and the repeated ids of a Zipf vocabulary
collide, so it costs tens of ns a row while doing almost no arithmetic. The
same sums are ``onehot(tokens)ᵀ @ dx``, a dense matmul the MXU runs at the
shape of the output head's ``dW``. This kernel sweeps the table's rows in
``block_v`` chunks (one grid step each); for each it builds the chunk's
one-hot ``(block_v, R)`` in VMEM, in ``dx``'s dtype (exact for 0 and 1),
and writes ``onehot @ dx`` with f32 accumulation into the chunk's rows of
the output. The one-hot never reaches HBM.

Each product is ``1 × dx`` and exact; only the order of the f32 sums differs
from the scatter's. An id outside ``[0, rows)`` matches no row and adds
nothing. The output does not depend on the table, so the table is no
operand.

Layout: ``tokens (1, R)`` int32 with ``R`` in lanes, ``dx (R, d)``, the
output ``(rows, d)`` f32; ``R`` a multiple of `ROW_TILE` and ``rows`` of
``block_v`` (itself a multiple of 128). ``tokens`` and ``dx`` stay resident across the grid.
Under ``vmap`` (the engine's client chunk) Pallas's batching rule adds the
client axis to the grid, one client's operands per step.
`ops.embed_lookup` is the supported padding path; ragged shapes fail loudly
here.

``interpret=None`` (default) auto-selects per backend: compiled Pallas on
TPU, the Pallas interpreter elsewhere, as in `kernels.lm_head_xent`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROW_TILE = 128       # token rows padded to whole lanes of the one-hot
BLOCK_V = 2048       # most table rows per grid step
VMEM_LIMIT = 64 * 1024 * 1024


def default_interpret() -> bool:
    """Backend auto-select: real Pallas on TPU, interpreter elsewhere."""
    return jax.default_backend() != "tpu"


def _kernel(tok_ref, dx_ref, out_ref, *, block_v):
    dx = dx_ref[...]
    # the chunk's ids relative to its first row, against a row iota
    tok = tok_ref[...] - pl.program_id(0) * block_v          # (1, R)
    ids = jax.lax.broadcasted_iota(jnp.int32, (block_v, tok.shape[1]), 0)
    onehot = (ids == tok).astype(dx.dtype)                   # (block_v, R)
    # f32 operands are split for exact products; bf16 ones are exact as is
    prec = (jax.lax.Precision.HIGHEST if dx.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    out_ref[...] = jnp.dot(onehot, dx, precision=prec,
                           preferred_element_type=jnp.float32)


def embed_grad(tokens, dx, *, rows: int, block_v: int = BLOCK_V,
               interpret=None):
    """``onehot(tokens)ᵀ @ dx``, (rows, d) f32: row ``v`` is the sum of the
    rows of ``dx`` whose token is ``v``."""
    R = tokens.shape[-1]
    if not (tokens.shape == (1, R) and dx.ndim == 2 and dx.shape[0] == R
            and R % ROW_TILE == 0 and block_v % LANES == 0
            and rows % block_v == 0):
        raise ValueError(
            f"embed_grad: operands must be tokens (1, R) and dx (R, d), "
            f"R a multiple of {ROW_TILE}, and rows a multiple of block_v "
            f"({block_v}, itself a multiple of {LANES}); see "
            f"repro.kernels.embed_grad.ops for the padding path — got "
            f"tokens {tuple(tokens.shape)}, dx {tuple(dx.shape)}, rows "
            f"{rows}")
    if interpret is None:
        interpret = default_interpret()
    d = dx.shape[1]
    return pl.pallas_call(
        functools.partial(_kernel, block_v=block_v),
        grid=(rows // block_v,),
        in_specs=[pl.BlockSpec((1, R), lambda j: (0, 0)),
                  pl.BlockSpec((R, d), lambda j: (0, 0))],
        out_specs=pl.BlockSpec((block_v, d), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tokens.astype(jnp.int32), dx)

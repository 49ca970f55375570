"""jit-friendly wrapper: the embedding lookup with a custom VJP whose
backward is the one-hot matmul kernel of `embed_grad`.

``embed_lookup(table, tokens, compute_dtype)`` is exactly
``jnp.take(table, tokens, axis=0).astype(compute_dtype)`` (the reference
`ref.embed_lookup_ref`) in the forward. Its backward takes the ``(..., d)``
cotangent in the compute dtype and returns the table's gradient in the
table's dtype: the token rows are flattened and padded to the kernel's row
tile (padded rows get token −1 and a zero cotangent), and the table is swept
in the widest chunk of at most `BLOCK_V` rows that divides it (padded to
128 rows). Ids are read as `jnp.take` reads them: a negative id counts from
the end, and one still outside ``[0, Vpad)`` adds nothing.

Precision is the scatter's: each cotangent element is added once, exactly,
into an f32 sum; only the order of the f32 additions differs.

``interpret=None`` auto-selects per backend (compiled Pallas on TPU, the
Pallas interpreter elsewhere); the op and its VJP batch under ``vmap`` with
the table batched or not (the engine's client chunk makes the table
per-client).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.embed_grad import embed_grad as K


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, tokens, compute_dtype, interpret):
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)


def _lookup_fwd(table, tokens, compute_dtype, interpret):
    # the table rides along for its shape and dtype only
    return _lookup(table, tokens, compute_dtype, interpret), (table, tokens)


def _lookup_bwd(compute_dtype, interpret, res, ct):
    table, tokens = res
    V, d = table.shape
    tok = tokens.reshape(-1).astype(jnp.int32)
    tok = jnp.where(tok < 0, tok + V, tok)       # as jnp.take wraps them
    dx = ct.reshape(-1, d).astype(compute_dtype)
    pad = _round_up(tok.shape[0], K.ROW_TILE) - tok.shape[0]
    tok = jnp.pad(tok, (0, pad), constant_values=-1)[None, :]
    dx = jnp.pad(dx, ((0, pad), (0, 0)))
    Vp = _round_up(V, K.LANES)
    block_v = max(b for b in range(K.LANES, min(K.BLOCK_V, Vp) + 1, K.LANES)
                  if Vp % b == 0)
    dw = K.embed_grad(tok, dx, rows=Vp, block_v=block_v, interpret=interpret)
    return dw[:V].astype(table.dtype), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embed_lookup(table, tokens, compute_dtype, *, interpret=None):
    """Rows of ``table`` (Vpad, d) at ``tokens`` (...) int, as (..., d) in
    ``compute_dtype``; the table's gradient is the one-hot matmul kernel's.
    """
    if table.ndim != 2 or not jnp.issubdtype(tokens.dtype, jnp.integer):
        raise ValueError(
            f"embed_lookup: expected table (Vpad, d) and integer tokens — "
            f"got table {tuple(table.shape)}, tokens {tokens.dtype}")
    if interpret is None:
        interpret = K.default_interpret()
    return _lookup(table, tokens, jnp.dtype(compute_dtype), bool(interpret))

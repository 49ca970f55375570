"""Reference for the embedding lookup and its gradient: the model's XLA path
as it stands, `jnp.take` followed by ``astype``, differentiated by autodiff
(a scatter-add of the token rows' cotangents)."""
from __future__ import annotations

import jax.numpy as jnp


def embed_lookup_ref(table, tokens, compute_dtype):
    """table: (Vpad, d); tokens: (...) int. Returns (..., d) in
    ``compute_dtype``."""
    return jnp.take(table, tokens, axis=0).astype(compute_dtype)

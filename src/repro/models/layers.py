"""Shared neural-net layers — raw JAX, pytree params, bf16-compute/f32-param.

Everything here is a pure function over explicit param pytrees so that the DP
machinery (which clips/averages/noises *update pytrees*) composes with any
architecture in the zoo.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_dim: Optional[int] = None, dtype=jnp.float32):
    """Truncated-normal fan-in init."""
    if in_dim is None:
        in_dim = shape[0]
    std = 1.0 / math.sqrt(in_dim)
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * 0.02


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x, w, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, w, b, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


def norm_init(d: int, kind: str):
    if kind == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """Apply RoPE. x: (..., S, H, hd); positions: (..., S) int32."""
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_position_at(pos, d: int):
    """PE row for a single (traced) position. Returns (1, d) f32."""
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10_000.0, dim / d)  # (d/2,)
    pe = jnp.stack([jnp.sin(ang), jnp.cos(ang)], axis=-1).reshape(1, d)
    return pe


def sinusoidal_positions(seq_len: int, d: int):
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    pe = jnp.zeros((seq_len, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe


# ---------------------------------------------------------------------------
# attention (GQA, causal / sliding-window / bidirectional, query-chunked)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attend_block(q, k, v, q_pos, kv_pos, kv_len, window, causal):
    """One (all-queries-in-block × all-kv) attention. q: (B,Sq,H,hd),
    k/v: (B,Skv,KV,hd). Returns (B,Sq,H,hd). Softmax in f32."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    # scores: (B, KV, G, Sq, Skv)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (1.0 / math.sqrt(hd))
    valid = kv_pos[None, :] < kv_len if kv_len is not None else jnp.ones(
        (1, k.shape[1]), bool)
    valid = valid & (kv_pos[None, :] >= 0)  # ring-buffer slots can be empty
    if causal:
        valid = valid & (kv_pos[None, :] <= q_pos[:, None])
    if window and window > 0:
        valid = valid & (kv_pos[None, :] > q_pos[:, None] - window)
    scores = jnp.where(valid[None, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out.reshape(B, Sq, H, hd)


def attention(q, k, v, *, q_positions, kv_positions, kv_len=None,
              causal=True, window: int = 0, q_chunk: int = 1024):
    """GQA attention, chunked over queries to bound the score transient.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd).
    q_positions: (Sq,), kv_positions: (Skv,) absolute positions.
    kv_len: scalar count of valid cache entries (None = all valid).
    """
    B, Sq, H, hd = q.shape
    if Sq <= q_chunk:
        return _attend_block(q, k, v, q_positions, kv_positions, kv_len,
                             window, causal)
    pad = (-Sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad))
        out = attention(q, k, v, q_positions=q_positions,
                        kv_positions=kv_positions, kv_len=kv_len,
                        causal=causal, window=window, q_chunk=q_chunk)
        return out[:, :Sq]
    n = Sq // q_chunk
    qs = q.reshape(B, n, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    ps = q_positions.reshape(n, q_chunk)

    def body(_, qp):
        qc, pc = qp
        out = _attend_block(qc, k, v, pc, kv_positions, kv_len, window, causal)
        return None, out

    # remat per chunk: the backward pass recomputes one chunk's scores at a
    # time instead of saving (q_chunk × Skv) softmax residuals per chunk.
    _, outs = jax.lax.scan(jax.checkpoint(body), None, (qs, ps))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def gqa_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d_model, n_heads * head_dim)),
        "wk": dense_init(k2, (d_model, n_kv * head_dim)),
        "wv": dense_init(k3, (d_model, n_kv * head_dim)),
        "wo": dense_init(k4, (n_heads * head_dim, d_model), in_dim=n_heads * head_dim),
    }


def gqa_project(x, p, n_heads: int, n_kv: int, head_dim: int, positions, theta):
    """x: (B,S,d) → q (B,S,H,hd), k,v (B,S,KV,hd), RoPE applied."""
    B, S, _ = x.shape
    cd = x.dtype
    q = (x @ p["wq"].astype(cd)).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"].astype(cd)).reshape(B, S, n_kv, head_dim)
    v = (x @ p["wv"].astype(cd)).reshape(B, S, n_kv, head_dim)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    return q, k, v


# ---------------------------------------------------------------------------
# sharding hints (no-ops outside a mesh context)
# ---------------------------------------------------------------------------


def shard_hint(x, *spec):
    """with_sharding_constraint that only applies when a mesh is in scope and
    every named axis exists + divides — so model code can annotate hot
    activations (MoE dispatch, per-client grads) without coupling tests or
    CPU runs to a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    names = dict(mesh.shape_tuple)

    # drop axis names that don't exist / don't divide (entry-wise fallback)
    def fit(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if a in names)
        while axes:
            par = 1
            for a in axes:
                par *= names[a]
            if dim % par == 0:
                return axes if len(axes) > 1 else axes[0]
            axes = axes[1:]
        return None

    if len(spec) != x.ndim:
        return x
    fitted = [fit(e, d) for e, d in zip(spec, x.shape)]
    from jax.sharding import PartitionSpec
    return jax.lax.with_sharding_constraint(x, PartitionSpec(*fitted))


# ---------------------------------------------------------------------------
# sliding-window ring-buffer KV cache helpers
# ---------------------------------------------------------------------------
# For window-attention decode the cache is a ring of W = attn_window slots:
# position p lives in slot p % W, so a 500k-token context needs only W slots
# (0.8% of the bytes at W=4096). Slot→position recovery is arithmetic.


def ring_positions(pos, W: int):
    """Absolute position held by each of the W ring slots at decode step
    ``pos`` (the new token's position). Negative ⇒ slot still empty."""
    i = jnp.arange(W, dtype=jnp.int32)
    return pos - jnp.mod(pos - i, W)


def ring_pack(kv, W: int, axis: int = 2):
    """Pack the last W positions of a (..., S, ...) prefill KV stack into
    ring order (slot = position % W)."""
    S = kv.shape[axis]
    if S <= W:
        return kv
    sliced = jax.lax.slice_in_dim(kv, S - W, S, axis=axis)
    return jnp.roll(sliced, S % W, axis=axis)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff)),
        "w_up": dense_init(k2, (d_model, d_ff)),
        "w_down": dense_init(k3, (d_ff, d_model), in_dim=d_ff),
    }


def swiglu(x, p):
    cd = x.dtype
    g = jax.nn.silu(x @ p["w_gate"].astype(cd))
    u = x @ p["w_up"].astype(cd)
    return (g * u) @ p["w_down"].astype(cd)


def gelu_mlp_init(key, d_model: int, d_ff: int):
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_in": dense_init(k1, (d_model, d_ff)),
        "b_in": jnp.zeros((d_ff,), jnp.float32),
        "w_out": dense_init(k2, (d_ff, d_model), in_dim=d_ff),
        "b_out": jnp.zeros((d_model,), jnp.float32),
    }


def gelu_mlp(x, p):
    cd = x.dtype
    h = jax.nn.gelu(x @ p["w_in"].astype(cd) + p["b_in"].astype(cd))
    return h @ p["w_out"].astype(cd) + p["b_out"].astype(cd)


def mlp_init(key, d_model: int, d_ff: int, act: str):
    return swiglu_init(key, d_model, d_ff) if act == "swiglu" else gelu_mlp_init(key, d_model, d_ff)


def mlp(x, p, act: str):
    return swiglu(x, p) if act == "swiglu" else gelu_mlp(x, p)


# ---------------------------------------------------------------------------
# vocab padding + loss
# ---------------------------------------------------------------------------


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


def lm_loss(logits, labels, vocab: int, mask=None):
    """Cross-entropy over a (possibly padded) vocab axis. logits: (B,S,Vpad) —
    may be sharded on Vpad; everything here is elementwise or a reduction over
    that axis, so it lowers to partial reductions + a small psum under GSPMD.
    labels: (B,S) int32. mask: (B,S) float or None."""
    Vpad = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    if Vpad > vocab:
        pad_mask = jax.lax.broadcasted_iota(jnp.int32, (Vpad,), 0) >= vocab
        lf = jnp.where(pad_mask[None, None, :], NEG_INF, lf)
    lse = jax.nn.logsumexp(lf, axis=-1)
    # one-hot contraction instead of take_along_axis: sharded-vocab friendly
    # (elementwise select + reduction over the vocab axis → partial sums +
    # psum under GSPMD). Written as a fused where-reduce rather than
    # materializing the one-hot and multiplying — bit-identical (the sum
    # has exactly one nonzero term either way), one less (B,S,Vpad) pass.
    vocab_ids = jax.lax.broadcasted_iota(jnp.int32, (Vpad,), 0)
    true_logit = jnp.sum(
        jnp.where(labels[..., None] == vocab_ids, lf, 0.0), axis=-1)
    nll = lse - true_logit
    if mask is None:
        return jnp.mean(nll)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

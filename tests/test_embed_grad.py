"""The embedding lookup with the one-hot matmul gradient
(`repro.kernels.embed_grad`) against its reference, ``jnp.take`` followed by
``astype`` under autodiff: the value and the table's gradient, in the Pallas
interpreter at small sizes; and the CIFG-LSTM's loss gradient on the
"fused" path against the "seq" path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.embed_grad import embed_grad as K
from repro.kernels.embed_grad.ops import embed_lookup
from repro.kernels.embed_grad.ref import embed_lookup_ref
from repro.models import build
from repro.models.layers import pad_vocab

KEY = jax.random.PRNGKey(5)
D = 24
VOCAB = 300                      # the table holds pad_vocab(300) = 512 rows
CLIENTS = 3

# (compute dtype, table rows, ids, wrap)
CASES = {
    "f32": ("float32", 512, "uniform", "plain"),
    "bf16": ("bfloat16", 512, "uniform", "plain"),
    "rows-not-lane-multiple": ("bfloat16", 300, "uniform", "plain"),
    "repeated-ids": ("bfloat16", 512, "repeated", "plain"),
    "all-id-0": ("float32", 512, "zero", "plain"),
    "padded-table-rows": ("bfloat16", 512, "padding", "plain"),
    "out-of-range": ("float32", 512, "out_of_range", "plain"),
    "masked-rows": ("bfloat16", 512, "uniform", "masked"),
    "vmap-shared-table": ("bfloat16", 512, "repeated", "vmap_shared"),
    "vmap-batched-table": ("float32", 512, "uniform", "vmap_batched"),
    "jit": ("bfloat16", 512, "repeated", "jit"),
}


def _tokens(kind, rows, shape, key):
    if kind == "zero":
        return jnp.zeros(shape, jnp.int32)
    if kind == "repeated":           # a handful of ids, each many times
        return jax.random.randint(key, shape, 0, 4) * 7
    if kind == "padding":            # ids past the vocabulary, in the table
        return jax.random.randint(key, shape, VOCAB, rows)
    ids = jax.random.randint(key, shape, 0, min(rows, VOCAB))
    if kind == "out_of_range":       # past the end, before the start, and
        # negative ids that count from the end as jnp.take's do
        ids = ids.at[..., 0, :3].set(jnp.array([rows, rows + 9, -rows - 1]))
        ids = ids.at[..., 1, :2].set(jnp.array([-1, -rows]))
    return ids


def _inputs(cd, rows, kind, wrap):
    k = jax.random.split(KEY, 3)
    lead = (CLIENTS,) if wrap.startswith("vmap") else ()
    B, S = 3, 7                      # 21 rows: the row padding is exercised
    table = jax.random.normal(k[0], lead * (wrap == "vmap_batched")
                              + (rows, D))
    tokens = _tokens(kind, rows, lead + (B, S), k[1])
    ct = jax.random.normal(k[2], lead + (B, S, D)).astype(cd)
    if wrap == "masked":             # a masked row's cotangent is zero
        ct = ct.at[0].set(0.0).at[2, 3:].set(0.0)
    return table, tokens, ct


@pytest.mark.parametrize("case", list(CASES))
def test_embed_lookup_matches_reference(case, monkeypatch):
    cd, rows, kind, wrap = CASES[case]
    monkeypatch.setattr(K, "BLOCK_V", 128)        # several grid steps
    table, tokens, ct = _inputs(cd, rows, kind, wrap)

    def value_and_grad(lookup):
        def one(table, tokens, ct):
            y, vjp = jax.vjp(lambda t: lookup(t, tokens, cd), table)
            return y, vjp(ct)[0]
        if wrap == "jit":
            return jax.jit(one)
        if wrap.startswith("vmap"):
            return jax.vmap(one, in_axes=(0 if wrap == "vmap_batched"
                                          else None, 0, 0))
        return one

    y, dw = value_and_grad(embed_lookup)(table, tokens, ct)
    want_y, want_dw = value_and_grad(embed_lookup_ref)(table, tokens, ct)

    assert y.dtype == jnp.dtype(cd) and dw.dtype == table.dtype
    assert dw.shape == want_dw.shape
    # the forward is the same take: equal, out-of-range fills included
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(want_y, np.float32))
    # every product is exact; only the order of the f32 sums differs
    scale = max(float(jnp.abs(want_dw).max()), 1e-30)
    assert float(jnp.abs(dw - want_dw).max()) <= 1e-6 * scale
    if kind == "zero":
        assert not np.any(np.asarray(dw)[..., 1:, :])


def test_embed_grad_refuses_ragged_operands():
    with pytest.raises(ValueError, match="multiple of"):
        K.embed_grad(jnp.zeros((1, 100), jnp.int32), jnp.zeros((100, D)),
                     rows=256, block_v=128)


def test_model_grad_fused_matches_seq():
    """``jax.grad(loss_fn)`` on the "fused" path (Pallas cell, head and
    embedding gradient, interpreted) against the "seq" path (XLA's scatter
    for the embedding), every leaf, at a tiny width."""
    out = {}
    for path in ("seq", "fused"):
        cfg = get_config("gboard-cifg-lstm").with_(
            vocab=VOCAB, d_model=12, d_ff=20, cell_path=path,
            compute_dtype="float32")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(3))
        tokens = jax.random.randint(jax.random.fold_in(KEY, 5), (3, 7), 0,
                                    cfg.vocab)
        tokens = tokens.at[1, :4].set(2)          # repeated ids
        batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
                 "mask": jnp.ones((3, 6)).at[0, 4:].set(0.0)}
        out[path] = jax.value_and_grad(model.loss_fn)(params, batch)
    (ref_loss, ref_g), (loss, g) = out["seq"], out["fused"]
    assert ref_g["embed"]["tok"].shape == (pad_vocab(VOCAB), 12)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (name, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6, err_msg=str(name))

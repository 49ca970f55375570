"""The fused head cross-entropy (`repro.kernels.lm_head_xent`) against its
reference, the tied-embedding logits followed by `layers.lm_loss` under
autodiff: loss, ``dy`` and ``dw``, in the Pallas interpreter at small sizes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.lm_head_xent import lm_head_xent as K
from repro.kernels.lm_head_xent.ops import lm_head_xent
from repro.kernels.lm_head_xent.ref import lm_head_xent_ref
from repro.models import build
from repro.models.layers import pad_vocab

KEY = jax.random.PRNGKey(11)
D = 24
CLIENTS = 3

# (compute dtype, vocab, columns per chunk, mask, wrap, rows per grid step)
CASES = {
    "f32": ("float32", 256, 128, "some_zero", "plain", None),
    "bf16": ("bfloat16", 256, 128, "some_zero", "plain", None),
    "f32-pad-columns": ("float32", 300, 128, "some_zero", "plain", None),
    "bf16-pad-columns": ("bfloat16", 300, 128, "some_zero", "plain", None),
    "no-mask": ("float32", 300, 256, None, "plain", None),
    "all-zero-mask": ("float32", 300, 128, "all_zero", "plain", None),
    "row-blocks": ("bfloat16", 300, 128, "some_zero", "plain", 16),
    "vmap-shared-w": ("bfloat16", 300, 128, "some_zero", "vmap_shared",
                      None),
    "vmap-batched-w": ("bfloat16", 300, 128, "some_zero", "vmap_batched",
                       16),
    "jit": ("bfloat16", 300, 128, "some_zero", "jit", None),
}


def _inputs(cd, vocab, mask_kind, lead=()):
    k = jax.random.split(KEY, 4)
    B, S = 3, 7                      # 21 rows: the row padding is exercised
    y = jax.random.normal(k[0], lead + (B, S, D)).astype(cd)
    w = 0.5 * jax.random.normal(k[1], (pad_vocab(vocab), D))
    labels = jax.random.randint(k[2], lead + (B, S), 0, vocab)
    if mask_kind is None:
        return y, w, labels, None
    mask = (jax.random.uniform(k[3], lead + (B, S)) > 0.3)
    mask = mask.at[..., 0, :].set(False)          # a whole row masked
    if mask_kind == "all_zero":
        mask = jnp.zeros_like(mask)
    return y, w, labels, mask.astype(jnp.float32)


def _close(got, want, cd, what):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    # bf16: the reference rounds dw to bf16 and the kernel rounds g; f32
    # differs only in the order of the f32 sums
    tol = 1e-2 if cd == "bfloat16" else 1e-5
    scale = max(float(np.abs(want).max()), 1e-30)
    assert np.abs(got - want).max() <= tol * scale, what


@pytest.mark.parametrize("case", list(CASES))
def test_fused_head_matches_reference(case, monkeypatch):
    cd, vocab, block_v, mask_kind, wrap, row_block = CASES[case]
    monkeypatch.setattr(K, "BLOCK_V", block_v)
    if row_block:                    # 21 rows in two blocks of 16
        monkeypatch.setattr(K, "ROW_BLOCK", row_block)
    lead = (CLIENTS,) if wrap.startswith("vmap") else ()
    y, w, labels, mask = _inputs(cd, vocab, mask_kind, lead)

    def fused(y, w, labels, mask):
        return lm_head_xent(y, w, labels, mask, vocab=vocab)

    def ref(y, w, labels, mask):
        return lm_head_xent_ref(y, w, labels, mask, vocab=vocab)

    got_fn = jax.value_and_grad(fused, argnums=(0, 1))
    want_fn = jax.value_and_grad(ref, argnums=(0, 1))
    if wrap == "jit":
        got_fn = jax.jit(got_fn)
    if wrap.startswith("vmap"):
        if wrap == "vmap_batched":
            w = w * (1.0 + jnp.arange(CLIENTS, dtype=jnp.float32)
                     )[:, None, None]
        axes = (0, 0 if wrap == "vmap_batched" else None, 0, 0)
        got_fn = jax.vmap(got_fn, in_axes=axes)
        want_fn = jax.vmap(want_fn, in_axes=axes)
    loss, (dy, dw) = got_fn(y, w, labels, mask)
    want_loss, (want_dy, want_dw) = want_fn(y, w, labels, mask)

    assert dy.dtype == y.dtype and dw.dtype == w.dtype
    np.testing.assert_allclose(np.asarray(loss), np.asarray(want_loss),
                               rtol=1e-5, atol=1e-7)
    _close(dy, want_dy, cd, "dy")
    _close(dw, want_dw, cd, "dw")
    # the table's padding rows get no gradient, as under the mask of lm_loss
    assert not np.any(np.asarray(dw)[..., vocab:, :])
    if mask_kind == "all_zero":
        assert float(jnp.abs(loss).max()) == 0.0
        assert not np.any(np.asarray(dy, np.float32))


def test_model_loss_fused_matches_ref():
    """``loss_fn`` on the "fused" path (Pallas cell and head) against the
    "ref" path, loss and every gradient, at a tiny width."""
    out = {}
    for path in ("ref", "fused"):
        cfg = get_config("gboard-cifg-lstm").with_(
            vocab=300, d_model=12, d_ff=20, cell_path=path,
            compute_dtype="float32")
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(3))
        tokens = jax.random.randint(jax.random.fold_in(KEY, 5), (3, 7), 0,
                                    cfg.vocab)
        batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
                 "mask": jnp.ones((3, 6)).at[0, 4:].set(0.0)}
        out[path] = jax.value_and_grad(model.loss_fn)(params, batch)
    (ref_loss, ref_g), (loss, g) = out["ref"], out["fused"]
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for (name, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6, err_msg=str(name))

"""Reference for the fused head cross-entropy: the model's XLA path as it
stands, the tied-embedding logits (`repro.models.embed.lm_logits`) followed
by `repro.models.layers.lm_loss`, differentiated by autodiff."""
from __future__ import annotations

from repro.models.embed import lm_logits
from repro.models.layers import lm_loss


def lm_head_xent_ref(y, w, labels, mask=None, *, vocab: int):
    """y: (B, S, d) in the compute dtype; w: (Vpad, d); labels, mask:
    (B, S). Returns the scalar loss."""
    return lm_loss(lm_logits({"tok": w}, y), labels, vocab, mask)

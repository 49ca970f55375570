"""Fused tied-embedding head and cross-entropy for the client step: the
Pallas kernels (`lm_head_xent`), the custom-VJP op (`ops.lm_head_xent`)
and its reference (`ref.lm_head_xent_ref`)."""

"""Input-embedding gradient for the client step: the Pallas kernel
(`embed_grad`), the custom-VJP lookup (`ops.embed_lookup`) and its
reference (`ref.embed_lookup_ref`)."""

"""Random CIFG-LSTM weights made from the seed, on the device, in one jitted
call, in the type they are trained and served in (float32). The benchmark
makes them, so the reference can take the same ones without taking anything
the program made."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

WEIGHTS_TAG = 1


def base_key(seed: int):
    """The run's root key; seeds past 32 bits wrap, as the chip's key does."""
    return jax.random.PRNGKey(int(seed) % (1 << 32))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _init(key, d: int, h: int, vocab_pad: int, embed_std: float):
    k_e, k_x, k_h, k_p = jax.random.split(key, 4)
    fan = 1.0 / jnp.sqrt(jnp.float32(d + h))

    def tn(k, shape, std):
        return std * jax.random.truncated_normal(k, -2.0, 2.0, shape,
                                                 jnp.float32)

    return {"embed": {"tok": embed_std * jax.random.normal(
                k_e, (vocab_pad, d), jnp.float32)},
            "w_x": tn(k_x, (d, 3 * h), fan),
            "w_h": tn(k_h, (h, 3 * h), fan),
            "b_gates": jnp.zeros((3 * h,), jnp.float32),
            "w_proj": tn(k_p, (h, d), 1.0 / jnp.sqrt(jnp.float32(h)))}


def cifg_params(seed: int, model: dict, device=None):
    """``model`` is the configuration's ``model`` group: ``embed_dim``,
    ``hidden``, ``vocab_pad`` and ``embed_std``."""
    key = jax.random.fold_in(base_key(seed), WEIGHTS_TAG)
    if device is not None:
        key = jax.device_put(key, device)
    params = _init(key, model["embed_dim"], model["hidden"],
                   model["vocab_pad"], float(model["embed_std"]))
    return jax.block_until_ready(params)

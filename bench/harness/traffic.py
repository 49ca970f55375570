"""The one traffic generator: it reads a mix's data file and makes the inputs
of a run from the seed. Two kinds of mix exist:

* ``sessions`` — open-loop keyboard sessions: arrival times, prompts and the
  number of suggestions each asks for;
* ``population`` — a federated population's on-device data: every user's
  example windows and example count.

Sizes are drawn as quantiles, so every seed gives the same multiset of gaps,
prompt lengths and suggestion counts in another order: the seed changes the
order and the token ids, not the amount of work.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.weights import base_key

BOS = 2
PAD = 0
FIRST_WORD = 4          # ids 0-3 are PAD, UNK, BOS, EOS
SESSIONS_TAG = 2
POPULATION_TAG = 3


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def geometric_lengths(n: int, median: int, lo: int, hi: int) -> np.ndarray:
    """Quantiles of 1 + Geometric(p), with p set so the median is
    ``median``, clipped to [lo, hi]."""
    q = 0.5 ** (1.0 / max(median - 1, 1))
    u = _quantiles(n)
    lens = 1 + np.floor(np.log1p(-u) / math.log(q)).astype(np.int64)
    return np.clip(lens, lo, hi)


def sessions(mix: Dict, seed: int, seconds: float, vocab: int) -> Dict:
    """Open-loop sessions due in ``[0, seconds)``: ``due`` (n,) seconds from
    the window's start, ``prompts`` (list of int32 arrays, BOS first) and
    ``steps`` (n,) suggestions asked for."""
    rate = float(mix["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng([int(seed), SESSIONS_TAG])
    gaps = -np.log1p(-_quantiles(n)) / rate         # exponential quantiles
    gaps = rng.permutation(gaps)
    due = np.cumsum(gaps) - gaps[0]
    due *= seconds * (1.0 - 0.5 / n) / max(due[-1] + gaps[0], 1e-12)
    pl = mix["prompt_len"]
    lens = rng.permutation(geometric_lengths(n, pl["median"], pl["min"],
                                             pl["max"]))
    sg = mix["suggestions"]
    width = sg["max"] - sg["min"] + 1
    steps = rng.permutation(sg["min"] + np.floor(
        _quantiles(n) * width).astype(np.int64))
    words = rng.integers(FIRST_WORD, vocab, size=int(lens.sum()))
    prompts: List[np.ndarray] = []
    at = 0
    for L in lens:
        p = np.empty(L, np.int32)
        p[0] = BOS
        p[1:] = words[at:at + L - 1]
        at += L - 1
        prompts.append(p)
    return {"due": due, "prompts": prompts, "steps": steps.astype(np.int64)}


def bucket_lengths(max_len: int) -> List[int]:
    """One prompt length per admission program the engine builds for
    prompts of at most ``max_len`` tokens: 1 (unpadded) and each power-of-two
    bucket 2, 4, ... that holds ``max_len``."""
    out, b = [1], 2
    while True:
        out.append(b // 2 + 1 if b > 2 else 2)
        if b >= max_len:
            return out
        b *= 2


def population(mix: Dict, seed: int, vocab: int, seq_len: int) -> Dict:
    """The base users' data, made on the device in one call and returned as
    host arrays: ``examples`` (U, E_max, seq_len+1) int32 windows (BOS,
    Zipf-distributed word ids, PAD after the sentence) and ``counts`` (U,)
    int32 examples each user holds. Users past the base repeat it
    (user u holds base user u mod U's data)."""
    ex = mix["examples_per_user"]
    sl = mix["sentence_tokens"]
    key = jax.random.fold_in(base_key(seed), POPULATION_TAG)
    examples, counts = _population(
        key, int(mix["base_users"]), int(ex["min"]), int(ex["max"]),
        int(sl["min"]), min(int(sl["max"]), seq_len + 1), seq_len + 1,
        vocab, float(mix["zipf_exponent"]))
    return {"examples": np.asarray(examples), "counts": np.asarray(counts)}


@partial(jax.jit, static_argnums=tuple(range(1, 9)))
def _population(key, users, e_min, e_max, l_min, l_max, row, vocab, zipf_a):
    k_c, k_l, k_w = jax.random.split(key, 3)
    counts = jax.random.randint(k_c, (users,), e_min, e_max + 1)
    lens = jax.random.randint(k_l, (users, e_max, 1), l_min, l_max + 1)
    ranks = jnp.arange(1, vocab - FIRST_WORD + 1, dtype=jnp.float32)
    cdf = jnp.cumsum(ranks ** -zipf_a)
    cdf = cdf / cdf[-1]
    u = jax.random.uniform(k_w, (users, e_max, row))
    words = (jnp.searchsorted(cdf, u) + FIRST_WORD).astype(jnp.int32)
    words = jnp.minimum(words, vocab - 1)
    pos = jnp.arange(row)[None, None, :]
    rows = jnp.where(pos == 0, BOS, jnp.where(pos < lens, words, PAD))
    return rows.astype(jnp.int32), counts.astype(jnp.int32)

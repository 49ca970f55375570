"""Plain reference of the CIFG-LSTM next-word model and of DP-FedAvg rounds.

Written from the paper's description (arXiv:2009.10031 §III-A; CIFG from
arXiv:1402.1128) in straightforward ``jax.numpy``, imports nothing of the
system under test and takes none of its weights or tables: the benchmark
makes the weights (``bench.harness.weights``) and the data
(``bench.harness.traffic``) from the seed and hands the same ones to both.

Model: a tied embedding ``tok`` (Vpad, d); the input projection
``x @ w_x + b_gates``; a CIFG recurrence over packed gates ``[f | o | g]``
with forget bias 1 and input gate ``1 - f``; a projection ``h @ w_proj``
back to ``d``; logits ``y @ tok.T`` over the first ``vocab`` rows.

``precision`` is ``"f32"`` (float32 matmuls at ``HIGHEST``, the reference)
or ``"fp8"``: every matmul operand is rounded to float8 e4m3 with a
per-tensor scale before a float32 product. The second is the control, one
precision step below the configuration's bfloat16 compute; it has to fail
the comparison.

DP-FedAvg (Algorithm 1): each client takes ``local_batches`` SGD steps on
its own batch, its delta is clipped to L2 norm ``S``, the cohort's clipped
deltas are summed and divided by the cohort size, Gaussian noise of standard
deviation ``z·S/cohort`` is added, and the server applies Nesterov momentum.
The random draws follow the DP mechanism's key schedule, from the seed: per
round ``key, k_avail, k_sample, k_idx, k_noise = split(key, 5)``; slot ``s``
draws its examples uniformly from its user's ``count`` with
``split(k_idx, cohort)[s]``; the noise of leaf ``i`` (leaves in sorted-path
order) is ``normal(split(k_noise, n_leaves)[i])``.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

PAD = 0
FORGET_BIAS = 1.0
# fixed leaf order: the sorted paths of the parameter dict
LEAVES = ("b_gates", "embed/tok", "w_h", "w_proj", "w_x")
HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0   # largest finite float8 e4m3fn


def _round(a, precision: str):
    """The operand as the matmul sees it at ``precision``."""
    if precision == "f32":
        return a
    if precision != "fp8":
        raise ValueError(
            f"precision must be 'f32' or 'fp8', got {precision!r}")
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / FP8_MAX
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=HIGHEST)


def get_leaf(params: Dict, path: str):
    node = params
    for k in path.split("/"):
        node = node[k]
    return node


def hidden_states(params, tokens, precision: str = "f32"):
    """tokens (B, S) int32 → hidden states (B, S, h) f32."""
    tok = params["embed"]["tok"]
    x = tok[tokens]                                       # (B, S, d)
    B, S, d = x.shape
    zx = _mm(x.reshape(B * S, d), params["w_x"], precision).reshape(
        B, S, -1) + params["b_gates"]
    h_dim = params["w_h"].shape[0]
    w_h = _round(params["w_h"], precision)

    def step(carry, zx_t):
        h, c = carry
        z = zx_t + jnp.matmul(_round(h, precision), w_h, precision=HIGHEST)
        f = jax.nn.sigmoid(z[:, :h_dim] + FORGET_BIAS)
        o = jax.nn.sigmoid(z[:, h_dim:2 * h_dim])
        g = jnp.tanh(z[:, 2 * h_dim:])
        c = f * c + (1.0 - f) * g
        h = o * jnp.tanh(c)
        return (h, c), h

    zeros = jnp.zeros((B, h_dim), jnp.float32)
    _, hs = jax.lax.scan(step, (zeros, zeros), zx.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def logits(params, tokens, vocab: int, precision: str = "f32"):
    """tokens (B, S) → next-token logits (B, S, vocab) f32."""
    hs = hidden_states(params, tokens, precision)
    B, S, h = hs.shape
    y = _mm(hs.reshape(B * S, h), params["w_proj"], precision)
    emb = params["embed"]["tok"][:vocab]
    return _mm(y, emb.T, precision).reshape(B, S, vocab)


def loss(params, rows, vocab: int, precision: str = "f32"):
    """Mean next-token cross-entropy of (B, S+1) example rows over the
    positions whose label is not PAD."""
    tokens, labels = rows[:, :-1], rows[:, 1:]
    lg = logits(params, tokens, vocab, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    true = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    mask = (labels != PAD).astype(jnp.float32)
    return jnp.sum((lse - true) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# ------------------------------------------------------------ DP-FedAvg


def client_delta(params, batches, vocab: int, lr: float, precision: str):
    """Local SGD over ``batches`` (nb, B, S+1): Δ = θ_local − θ, and the mean
    loss of the batches before each step."""
    def sgd(p, rows):
        val, g = jax.value_and_grad(loss)(p, rows, vocab, precision)
        return jax.tree_util.tree_map(lambda w, gw: w - lr * gw, p, g), val

    local, losses = jax.lax.scan(sgd, params, batches)
    delta = jax.tree_util.tree_map(jnp.subtract, local, params)
    return delta, jnp.mean(losses)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(l))
                        for l in jax.tree_util.tree_leaves(tree)))


def block_sum(params, batches, vocab: int, lr: float, clip: float,
              precision: str):
    """Clipped-delta sum and loss sum of a block of clients; batches is
    (clients, nb, B, S+1)."""
    def one(b):
        delta, l = client_delta(params, b, vocab, lr, precision)
        factor = jnp.minimum(1.0, clip / jnp.maximum(_global_norm(delta),
                                                     1e-12))
        return jax.tree_util.tree_map(lambda x: x * factor, delta), l

    deltas, losses = jax.vmap(one)(batches)
    return (jax.tree_util.tree_map(lambda x: jnp.sum(x, axis=0), deltas),
            jnp.sum(losses))


_block_sum_j = jax.jit(block_sum, static_argnums=(2, 3, 4, 5))


def round_keys(seed: int, n_rounds: int):
    """(k_idx, k_noise) of each round from the seed's key chain."""
    key = jax.random.PRNGKey(seed)
    out = []
    for _ in range(n_rounds):
        key, _avail, _sample, k_idx, k_noise = jax.random.split(key, 5)
        out.append((k_idx, k_noise))
    return out


def example_indices(k_idx, counts: np.ndarray, need: int) -> np.ndarray:
    """(cohort, need) indices each slot draws from its user's examples."""
    keys = jax.random.split(k_idx, counts.shape[0])
    draw = jax.vmap(lambda k, c: jax.random.randint(k, (need,), 0, c))
    return np.asarray(draw(keys, jnp.asarray(counts, jnp.int32)))


def fedavg_rounds(params0, cohorts: List[np.ndarray], cohort_size: int,
                  rows_of, counts_of, seed: int, *, vocab: int, lr: float,
                  clip: float, noise_multiplier: float, server_lr: float,
                  momentum: float, local_batches: int, batch_size: int,
                  precision: str = "f32", block: int = 50, devices=None):
    """Run ``len(cohorts)`` DP-FedAvg rounds from ``params0``.

    ``cohorts[r]`` holds the user ids of round ``r`` in slot order, padded
    past the first ``cohort_size`` slots, which alone take part; the padded
    length still sets how many slot keys are split. ``rows_of(ids)`` gives
    their (n, E_max, S+1) example rows and ``counts_of(ids)`` their example
    counts. Clients are
    processed ``block`` at a time, spread over ``devices``. Returns the
    per-round mean client loss, the server momentum after the first round
    (the first gradient as the optimizer holds it) and the parameters after
    the last round, all as host numpy trees.
    """
    devices = devices or jax.devices()[:1]
    need = local_batches * batch_size
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_m = [], None
    for r, (k_idx, k_noise) in enumerate(round_keys(seed, len(cohorts))):
        ids = np.asarray(cohorts[r])
        idx = example_indices(k_idx, counts_of(ids), need)
        n = cohort_size
        parts, d = [], 0
        for lo in range(0, n, block):
            hi = min(lo + block, n)
            rows = rows_of(ids[lo:hi])                    # (b, E, S+1)
            sel = np.take_along_axis(rows, idx[lo:hi, :, None], axis=1)
            batches = sel.reshape(hi - lo, local_batches, batch_size, -1)
            dev = devices[d % len(devices)]
            d += 1
            p_dev = jax.device_put(params, dev)
            parts.append(_block_sum_j(p_dev, jax.device_put(batches, dev),
                                      vocab, lr, clip, precision))
        home = devices[0]
        total = jax.tree_util.tree_map(
            lambda *xs: sum(jax.device_put(x, home) for x in xs),
            *[p[0] for p in parts])
        loss_sum = sum(jax.device_put(p[1], home) for p in parts)
        losses.append(float(loss_sum) / n)
        sigma = noise_multiplier * clip / n
        nkeys = jax.random.split(k_noise, len(LEAVES))
        noise = {}
        for k, path in zip(nkeys, LEAVES):
            leaf = get_leaf(total, path)
            noise[path] = jax.random.normal(k, leaf.shape, jnp.float32) * sigma
        mean = jax.tree_util.tree_map(lambda x: x / n, total)
        delta = _from_paths({p: get_leaf(mean, p) + noise[p] for p in LEAVES})
        mom = jax.tree_util.tree_map(lambda m, g: momentum * m + g, mom, delta)
        step = jax.tree_util.tree_map(lambda m, g: momentum * m + g, mom,
                                      delta)
        params = jax.tree_util.tree_map(lambda p, s: p + server_lr * s,
                                        params, step)
        if r == 0:
            first_m = jax.device_get(mom)
    return losses, first_m, jax.device_get(params)


def _from_paths(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        keys = path.split("/")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = v
    return out


# ------------------------------------------------------------ serving


def served_gaps(params, prompts: List[np.ndarray], served: List[np.ndarray],
                candidates: List[np.ndarray], vocab: int,
                precision: str = "f32", pick: str = "served"):
    """Teacher-force each prompt with its served tokens through the f32
    reference and return two arrays with one entry per served position:
    how far below the reference's best logit the chosen token's logit lies,
    and, over the ranks ``k`` of the suggestion strip, the widest gap by
    which the rank-``k`` candidate's logit lies below the reference's
    ``k``-th best.

    ``pick="served"`` chooses the served token and candidates (the
    program's answers, ``candidates[i]`` of shape (len(served[i]), K));
    ``pick="argmax"`` chooses the first token and the top ``K`` at
    ``precision`` (the control's answers at the same positions).
    """
    lens = [len(p) + len(s) - 1 for p, s in zip(prompts, served)]
    S = max(lens)
    n = len(prompts)
    toks = np.zeros((n, S), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, s[:-1]]).astype(np.int32)
        toks[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(logits, static_argnums=(2, 3))(
            params, jnp.asarray(toks), vocab, "f32"))
        low = (ref if precision == "f32" else np.asarray(
            jax.jit(logits, static_argnums=(2, 3))(
                params, jnp.asarray(toks), vocab, precision)))
    token_gaps, cand_gaps = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        K = np.asarray(candidates[i]).shape[-1]
        for j in range(len(s)):
            pos = len(p) - 1 + j
            row = ref[i, pos]
            if pick == "served":
                chosen = int(s[j])
                cands = np.asarray(candidates[i][j], np.int64)
            else:
                order = np.argsort(-low[i, pos], kind="stable")
                chosen, cands = int(order[0]), order[:K]
            best_k = -np.sort(-row)[:K]
            token_gaps.append(float(row.max() - row[chosen]))
            cand_gaps.append(float(np.max(best_k - row[cands])))
    return np.asarray(token_gaps), np.asarray(cand_gaps)

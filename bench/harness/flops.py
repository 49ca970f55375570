"""Operations and bytes the algorithms need, computed from shapes.

These counts belong to the benchmark, not to the program: they stay the same
whatever implements a layer, so a share of a roofline or of the peak means
the same thing before and after a change. Recomputation is not counted.
"""
from __future__ import annotations

import numpy as np

PAD = 0
F32 = 4
BF16 = 2


def cifg_lm_forward_flops_per_token(d: int, h: int, vocab: int) -> int:
    """Multiply-adds ×2 of one position of the CIFG-LSTM language model:
    input projection d·3h, recurrent h·3h, projection h·d, tied head d·V."""
    return 2 * (3 * (d + h) * h + h * d + d * vocab)


def cifg_lm_train_flops_per_token(d: int, h: int, vocab: int) -> int:
    """Forward and backward of one trained position: 3× the forward,
    6·(3·(d+h)·h + h·d + d·V)."""
    return 3 * cifg_lm_forward_flops_per_token(d, h, vocab)


def fl_round_tokens(cohort: int, local_batches: int, batch_size: int,
                    seq_len: int) -> int:
    """Positions one DP-FedAvg round computes: every client runs
    ``local_batches`` batches of ``batch_size`` windows of ``seq_len``,
    padding included."""
    return cohort * local_batches * batch_size * seq_len


def trained_tokens(rows: np.ndarray) -> int:
    """Positions that train in (..., S+1) example rows: those whose label,
    the next token, is not PAD. Padding is computed but trains nothing."""
    return int(np.count_nonzero(np.asarray(rows)[..., 1:] != PAD))


# elementwise operations per hidden unit of one CIFG step: three gate adds,
# the forget bias, two sigmoids and two tanh, c' = f·c + (1-f)·g (4), h = o·t
CIFG_CELL_ELEMENTWISE = 13


def cifg_cell_step(rows: int, h: int) -> tuple:
    """(FLOPs, bytes) of one CIFG cell step over ``rows`` rows: the h @ w_h
    product and the gate math; reads the gate inputs (3h, f32), w_h (bf16),
    h and c (f32), writes h and c (f32)."""
    flops = 2 * rows * h * 3 * h + CIFG_CELL_ELEMENTWISE * rows * h
    nbytes = rows * h * F32 * (3 + 2 + 2) + 3 * h * h * BF16
    return flops, nbytes


def dp_clip_client(n_params: int) -> tuple:
    """(FLOPs, bytes) of one client's clip → fold, as one algorithmic pass:
    read the delta once (sum of squares and scaled add share the read), read
    and write the f32 accumulator."""
    return 4 * n_params, 3 * n_params * F32


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")

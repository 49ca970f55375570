"""FLOP and byte counts and the peak table of the chip benchmark, against
numbers worked out by hand."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.drivers import fl
from bench.harness import flops, readers
from bench.harness.peaks import peaks_for

ROOT = Path(__file__).resolve().parents[2]


def load(rel):
    return json.loads((ROOT / rel).read_text())


def test_train_flops_per_token_of_the_paper_model():
    # 6·(3·(96+256)·256 + 256·96 + 96·10,000) = 6·1,254,912
    assert flops.cifg_lm_train_flops_per_token(96, 256, 10_000) == 7_529_472
    assert flops.cifg_lm_forward_flops_per_token(96, 256, 10_000) == \
        2 * 1_254_912


def test_fl_chip_share_round_trains_four_million_tokens():
    cfg = load("bench/configs/gboard_cifg_fl.json")
    mix = load("bench/traffic/chip_share.json")
    sz = fl.sizes(cfg, mix)
    assert sz == {"population": 1_000_000, "cohort": 5_000}
    assert flops.fl_round_tokens(sz["cohort"], mix["local_batches"],
                                 cfg["client"]["batch_size"],
                                 cfg["client"]["seq_len"]) == 4_000_000
    whole = fl.sizes(cfg, dict(mix, population_share=1.0))
    assert whole == {"population": 4_000_000, "cohort": 20_000}


def test_trained_tokens_leave_padding_out():
    # rows are BOS, words, then PAD; a position trains when its label is a
    # word: a sentence of 4 tokens after BOS trains 4 positions of 16
    rows = np.zeros((2, 3, 17), np.int32)
    rows[..., 0] = 2
    rows[0, 0, 1:5] = 7
    rows[0, 1, 1:17] = 9
    rows[1, 2, 1:2] = 5
    assert flops.trained_tokens(rows) == 4 + 16 + 1
    assert flops.trained_tokens(rows[:, :, :1]) == 0


def test_train_mfu_counts_trained_tokens_only():
    rec = {"rounds": 4, "trained_tokens": 10**6, "window_s": 2.0,
           "chips": 1, "device_kind": "TPU v5 lite", "embed_dim": 96,
           "hidden": 256, "vocab": 10_000}
    assert readers.train_mfu_pct(rec) == pytest.approx(
        100 * 10**6 * 7_529_472 / (2.0 * 197e12))


def test_dp_clip_counts_one_pass():
    # read the delta once, read and write the f32 accumulator: 12 B/param
    p = 10240 * 96 + 96 * 768 + 256 * 768 + 768 + 256 * 96
    assert p == 1_278_720
    f, b = flops.dp_clip_client(p)
    assert (f, b) == (4 * p, 12 * p)


def test_cifg_cell_step_counts():
    f, b = flops.cifg_cell_step(rows=8, h=256)
    assert f == 2 * 8 * 256 * 768 + 13 * 8 * 256
    assert b == 8 * 256 * 4 * 7 + 3 * 256 * 256 * 2


def test_roofline_picks_the_binding_bound():
    pk = peaks_for("TPU v5 lite")
    t, bound = flops.roofline_seconds(197e12, 1.0, pk)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9, pk)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_peak_table_refuses_an_unknown_device():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_shares_stay_silent_without_events():
    rec = {"trace": {"devices": [], "spans": [], "window": [0, 1]},
           "rounds_traced": 3, "device_kind": "TPU v5 lite", "cohort": 1,
           "local_batches": 1, "batch_size": 1, "seq_len": 1, "hidden": 8}
    assert readers.tick_mfu_pct(rec) is None

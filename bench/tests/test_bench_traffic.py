"""The chip benchmark's traffic generator repeats per seed, keeps the same
amount of work across seeds, and never sends a prompt longer than the
buckets the serving cell warms."""
import json
from pathlib import Path

import numpy as np

from bench.harness import traffic

ROOT = Path(__file__).resolve().parents[2]
TYPING = json.loads((ROOT / "bench/traffic/typing.json").read_text())
SEEDS = (0, 7, 2 ** 31 + 11)


def bucket(n):
    return 1 if n == 1 else 1 << (n - 1).bit_length()


def test_sessions_repeat_per_seed():
    a = traffic.sessions(TYPING, 2 ** 31 + 5, 10.0, 10_000)
    b = traffic.sessions(TYPING, 2 ** 31 + 5, 10.0, 10_000)
    c = traffic.sessions(TYPING, 2 ** 31 + 6, 10.0, 10_000)
    assert np.array_equal(a["due"], b["due"])
    assert np.array_equal(a["steps"], b["steps"])
    assert all(np.array_equal(p, q) for p, q in zip(a["prompts"],
                                                    b["prompts"]))
    assert not np.array_equal(a["due"], c["due"])


def test_every_seed_gets_the_same_work_in_another_order():
    runs = [traffic.sessions(TYPING, s, 10.0, 10_000) for s in SEEDS]
    n = len(runs[0]["due"])
    assert n == round(TYPING["rate_per_s"] * 10.0)
    for r in runs:
        assert len(r["due"]) == n
        assert np.all(np.diff(r["due"]) >= 0)
        assert 0.0 <= r["due"][0] and r["due"][-1] < 10.0
        assert sorted(map(len, r["prompts"])) == sorted(
            map(len, runs[0]["prompts"]))
        assert sorted(r["steps"]) == sorted(runs[0]["steps"])
        assert all(p[0] == traffic.BOS for p in r["prompts"])


def test_typing_prompts_stay_within_the_warmed_buckets():
    cap = TYPING["prompt_len"]["max"]
    assert cap == 32
    warmed = {bucket(n) for n in traffic.bucket_lengths(cap)}
    assert warmed == {1, 2, 4, 8, 16, 32}
    used = set()
    for s in SEEDS:
        lens = [len(p) for p in traffic.sessions(TYPING, s, 20.0,
                                                 10_000)["prompts"]]
        assert max(lens) <= cap
        used |= {bucket(n) for n in lens}
    assert used == warmed
    steps = traffic.sessions(TYPING, 3, 20.0, 10_000)["steps"]
    assert steps.min() == 1 and steps.max() == 8


def test_prompt_length_median_is_eight():
    lens = traffic.geometric_lengths(10_001, 8, 1, 32)
    assert int(np.median(lens)) == 8


def test_population_repeats_per_seed():
    mix = json.loads((ROOT / "bench/traffic/chip_share.json").read_text())
    mix.update(base_users=16)
    a = traffic.population(mix, 2 ** 31 + 3, 500, 16)
    b = traffic.population(mix, 2 ** 31 + 3, 500, 16)
    c = traffic.population(mix, 2 ** 31 + 4, 500, 16)
    assert np.array_equal(a["examples"], b["examples"])
    assert np.array_equal(a["counts"], b["counts"])
    assert not np.array_equal(a["examples"], c["examples"])
    ex = a["examples"]
    assert ex.shape == (16, 50, 17)
    assert np.all(ex[:, :, 0] == traffic.BOS)
    assert ex.max() < 500
    assert a["counts"].min() >= 10 and a["counts"].max() <= 50

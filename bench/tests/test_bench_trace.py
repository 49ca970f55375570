"""The trace reduction of the chip benchmark, on traces stored as fixtures:
busy union, kernel attribution, module time, collectives and idle gaps
labelled by the harness's spans."""
import json
from pathlib import Path

import pytest

from bench.harness import trace as T

FIXTURES = Path(__file__).parent / "fixtures"


def hand():
    return T.normalize(json.loads((FIXTURES / "trace_hand.json").read_text()))


def test_window_is_the_harness_span():
    t = hand()
    assert t["window"] == [0, 10000]
    assert T.window_s(t) == pytest.approx(1e-5)


def test_busy_is_the_union_of_ops_averaged_over_devices():
    # device 0: [1000,1700) ∪ [2000,2500) ∪ [5000,6000) = 2200 ns;
    # device 1: [1000,2000) = 1000 ns
    assert T.busy_s(hand()) == pytest.approx(1600e-9)


def test_kernel_time_by_name_and_module_attribution():
    t = hand()
    assert T.op_seconds(t, name="_fwd_kernel") == (pytest.approx(300e-9), 1)
    assert T.op_seconds(t, name="_sumsq_kernel|_clip_acc_kernel") == (
        pytest.approx(1000e-9), 1)
    # the module of an op that names none comes from the module interval
    # that holds its start
    sumsq = [o for o in t["devices"][0]["ops"] if o[0] == "_sumsq_kernel"]
    assert sumsq[0][3] == "jit__compute_body(9)"
    assert T.op_seconds(t, name="fusion", module="tick") == (
        pytest.approx(1500e-9), 2)


def test_module_seconds_count_program_events():
    assert T.module_seconds(hand(), r"_tick") == (pytest.approx(1900e-9), 2)


def test_collective_exposed_is_the_part_no_compute_covers():
    # device 0: all-gather [2000,2400) under fusion.2 [2300,2500): 300 ns
    # exposed; device 1 has no collective
    assert T.collective_exposed_s(hand()) == pytest.approx(150e-9)


def test_idle_gaps_are_labelled_by_the_innermost_span():
    gaps = T.idle_gaps(hand(), n=3)
    # device 0 idle: [0,1000) [1700,2000) [2500,5000) [6000,10000)
    assert [g[1] for g in gaps] == pytest.approx([4000e-9, 2500e-9,
                                                  1000e-9])
    assert [g[0] for g in gaps] == ["bench.window", "generator.wait",
                                    "engine.step"]


def test_top_ops_sum_over_devices():
    top = dict(T.top_ops(hand()))
    assert top["jit__tick/fusion.1"] == pytest.approx(1500e-9)


def test_interval_algebra():
    assert T.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert T.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


# A piece of a trace recorded on a TPU v5 lite chip: one serving engine step
# (an admission's prefill, sampler and scatter, then the decode tick) and
# the next session's submits, 200 device ops.
def recorded():
    return T.normalize(json.loads(
        (FIXTURES / "trace_v5e_serve.json").read_text()))


def busy_grid(t):
    """Independent count: mark every busy nanosecond of device 0."""
    import numpy as np
    lo, hi = (int(x) for x in t["window"])
    grid = np.zeros(hi - lo, bool)
    for _, s, d, _ in t["devices"][0]["ops"]:
        grid[max(int(s) - lo, 0):max(int(s + d) - lo, 0)] = True
    return grid, lo


def test_recorded_busy_matches_a_nanosecond_grid():
    t = recorded()
    grid, _ = busy_grid(t)
    assert T.busy_s(t) == pytest.approx(grid.sum() * 1e-9, rel=1e-6)
    assert 0 < T.busy_s(t) < T.window_s(t)


def test_recorded_gaps_match_the_grid_and_their_spans():
    import numpy as np
    t = recorded()
    grid, lo = busy_grid(t)
    edges = np.flatnonzero(np.diff(np.r_[1, grid.astype(np.int8), 1]))
    runs = sorted(((e - s, s) for s, e in zip(edges[::2], edges[1::2])),
                  reverse=True)[:3]
    gaps = T.idle_gaps(t, n=3)
    assert [g[1] for g in gaps] == pytest.approx([r[0] * 1e-9 for r in runs],
                                                 abs=2e-9)
    for (length, start), (label, _) in zip(runs, gaps):
        mid = lo + start + length / 2
        holding = [s for s in t["spans"] if s[1] <= mid <= s[1] + s[2]]
        want = min(holding, key=lambda s: s[2])[0] if holding else "none"
        assert label == want
    assert gaps[0][0] == "engine.step"


def test_recorded_ops_lie_in_their_programs():
    t = recorded()
    mods = t["devices"][0]["modules"]
    for name, s, d, module in t["devices"][0]["ops"]:
        assert any(m[0] == module and m[1] <= s and s + d <= m[1] + m[2] + 1
                   for m in mods), (name, module)
    assert T.module_seconds(t, r"_tick") == (pytest.approx(118160e-9), 1)
    seconds, count = T.op_seconds(t, module=r"_tick")
    assert count == 38 and 0 < seconds <= 118160e-9
    assert T.op_seconds(t, name=r"\[TopK\]")[1] == 2

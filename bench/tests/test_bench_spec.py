"""BENCHMARK.json names only files the benchmark has, and keeps to its
format: every cell's configuration, mix and metric readers exist, and
names, units and sentences stay within their limits."""
import json
import re
from pathlib import Path

import pytest

from bench.harness import spec

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_sentences():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group, e["name"]))
            for key in ("why", "layer", "source"):
                if key in e and group != "end_to_end" and not (
                        group == "per_layer" and key == "source"):
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert len(names) == len(set(names))


def test_every_cell_finds_its_files():
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        cfg = spec.config_of(ROOT, BENCH, w)
        assert (ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()
        assert spec.mix_of(ROOT, w)["kind"] in ("sessions", "population")
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert len(c["reduced"]) <= 16


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
        metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert callable(spec.reader(ROOT, metric))
    e2e = next(x for x in BENCH["end_to_end"] if x["name"] == m["moves"])
    for cell in m["workloads"]:
        assert spec.applies(e2e, cell)


def test_every_cell_reports_set_up_and_another_metric():
    for w in BENCH["workloads"]:
        e2e = spec.metric_names(BENCH, "end_to_end", w["name"])
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metric_names(BENCH, "per_layer", w["name"])


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25

"""``BENCHMARK.json`` and the files it names: cells, configurations, traffic
mixes, drivers and per-layer metric readers, each found by its name."""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional

from bench.harness import trace as trace_lib
from bench.harness.device import describe

def load_benchmark(root: Path) -> Dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json; "
                     f"known: {[w['name'] for w in bench['workloads']]}")


def config_of(root: Path, bench: Dict, cell_: Dict) -> Dict:
    for c in bench["configs"]:
        if c["name"] == cell_["config"]:
            return json.loads((Path(root) / c["file"]).read_text())
    raise SystemExit(f"bench: no configuration {cell_['config']!r}")


def mix_of(root: Path, cell_: Dict) -> Dict:
    p = Path(root) / "bench" / "traffic" / (cell_["traffic"] + ".json")
    if p.is_file():
        return json.loads(p.read_text())
    raise SystemExit(f"bench: no traffic mix {cell_['traffic']!r} under "
                     "bench/traffic")


def driver(config: Dict):
    return importlib.import_module(f"bench.drivers.{config['driver']}")


def applies(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def reader(root: Path, name: str):
    """The ``read(record)`` function of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def per_layer(root: Path, bench: Dict, cell_name: str, record: Dict
              ) -> Dict[str, Dict]:
    out = {}
    for m in bench["per_layer"]:
        if not applies(m, cell_name):
            continue
        value: Optional[float] = reader(root, m["name"])(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def end_to_end(bench: Dict, cell_name: str, values: Dict[str, float]
               ) -> Dict[str, Dict]:
    out = {}
    for m in bench["end_to_end"]:
        if applies(m, cell_name) and m["name"] in values:
            out[m["name"]] = {"value": float(values[m["name"]]),
                              "unit": m["unit"]}
    return out


def result_line(root: Path, bench: Dict, cell_: Dict, ctx, result) -> Dict:
    name = cell_["name"]
    device = describe(ctx.devices)
    device["memory_peak_bytes"] = int(result.memory_peak_bytes)
    line: Dict = {"correct": result.checks.correct,
                  "attempted": int(result.attempted),
                  "failed": int(result.failed)}
    if ctx.trace:
        t = result.trace
        line["metrics"] = per_layer(root, bench, name, result.record)
        device["busy_s"] = trace_lib.busy_s(t)
        device["window_s"] = trace_lib.window_s(t)
        line["device"] = device
        line["breakdown"] = {"device_ops": trace_lib.top_ops(t),
                             "idle_gaps": trace_lib.idle_gaps(t)}
    else:
        line["metrics"] = end_to_end(bench, name, result.end_to_end)
        line["device"] = device
    line["checks"] = result.checks.as_dict()
    return line


def metric_names(bench: Dict, kind: str, cell_name: str) -> List[str]:
    return [m["name"] for m in bench[kind] if applies(m, cell_name)]

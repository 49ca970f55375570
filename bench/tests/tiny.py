"""Small configurations and a CPU context for the benchmark's own tests:
the cells' files with sizes a test run can hold."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

import jax

from bench.harness.clock import CompileCounter
from bench.harness.context import Context

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))
_COUNTER = []


def load(rel: str) -> dict:
    return json.loads((ROOT / rel).read_text())


def fl_config() -> dict:
    cfg = load("bench/configs/gboard_cifg_fl.json")
    cfg["model"].update(vocab=200, vocab_pad=256, embed_dim=16, hidden=32)
    cfg["dp"].update(population=256, clients_per_round=32)
    cfg["client"].update(batch_size=4, seq_len=8)
    # read from the program's and the fp8 control's runs at this size on
    # the CPU: program at most 5e-07, control at least 8e-05
    cfg["limits"] = {"grad_gap": 1e-5, "change_gap": 1e-5}
    return cfg


def fl_mix(pods: int = 1, shards: int = 1) -> dict:
    mix = load("bench/traffic/chip_share.json")
    mix.update(population_share=0.5, base_users=64, rounds_per_call=2,
               reference_block=4, mesh={"pods": pods, "shards": shards})
    mix["examples_per_user"] = {"min": 2, "max": 6}
    mix["sentence_tokens"] = {"min": 3, "max": 9}
    return mix


def serve_config() -> dict:
    cfg = load("bench/configs/gboard_cifg_serve.json")
    cfg["model"].update(vocab=200, vocab_pad=256, embed_dim=16, hidden=32)
    cfg["engine"].update(max_slots=16)
    # read from the program's and the fp8 control's runs at this size on
    # the CPU, five seeds: logit_gap program at most 3.4e-04, control
    # 3.2e-03 to 3.6e-02; candidate_gap program at most 8.0e-04, control
    # at least 1.4e-02
    cfg["limits"] = {"logit_gap": 2e-3, "candidate_gap": 4e-3}
    return cfg


def serve_mix() -> dict:
    mix = load("bench/traffic/typing.json")
    mix.update(rate_per_s=40.0, checked_sessions=16)
    mix["prompt_len"] = {"median": 4, "min": 1, "max": 8}
    return mix


def context(config: dict, mix: dict, *, seed: int = 2 ** 31 + 7,
            seconds: float = 0.5, out_dir: str = "", devices=None) -> Context:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return Context(config=copy.deepcopy(config), mix=copy.deepcopy(mix),
                   seed=seed, seconds=seconds, trace=False,
                   devices=devices or jax.devices()[:1], out_dir=out_dir,
                   compiles=_COUNTER[0], t_process=time.perf_counter())

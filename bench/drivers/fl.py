"""Driver of the DP-FedAvg cells: ``SimEngine.run`` on the streamed
population backend with the sharded sampler, at the configuration's model
and DP recipe, on the mix's share of the deployment.

Set-up builds one engine and its state, and drives that same object through
the first rounds with the window's own call (``SimEngine.run``) and feed
(the population store). Those rounds are the ones the plain reference
follows once the window has closed. The window then runs blocks of
``rounds_per_call`` rounds until ``--seconds`` have passed; it ends at a
block boundary and no block is split or dropped.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Dict, List

import jax
import numpy as np

from bench.harness import compare, flops, program, traffic
from bench.harness.compare import Checks
from bench.harness.context import Context, Result
from bench.harness.device import memory_peak_bytes
from bench.harness.weights import cifg_params
from bench.reference import cifg_lstm as ref




def sizes(config: Dict, mix: Dict) -> Dict:
    """The run's population and cohort: the mix's share of the
    configuration's deployment."""
    dp = config["dp"]
    share = float(mix["population_share"])
    return {"population": int(round(dp["population"] * share)),
            "cohort": int(round(dp["clients_per_round"] * share))}


@dataclass
class Setup:
    engine: object
    state: object
    params0: Dict          # host copy of the initial weights
    losses: List[float]    # the program's loss of each checked round
    first_m: Dict          # server momentum after round 1, host
    params_n: Dict         # parameters after the checked rounds, host
    cohorts: List[np.ndarray]
    data: Dict
    cohort: int
    population: int


def setup(ctx: Context, alter=None) -> Setup:
    """Build the engine and its state and drive them through the checked
    rounds. ``alter`` is called with the engine before its first round, to
    plant a fault underneath (the fault tests)."""
    from repro.configs import ClientConfig, DPConfig
    from repro.data.population_store import (InMemoryPopulationStore,
                                             ReplicatedPopulationStore)
    from repro.fl.engine import SimEngine
    cfg, mix = ctx.config, ctx.mix
    m, dpc, cl = cfg["model"], cfg["dp"], cfg["client"]
    sz = sizes(cfg, mix)
    model = program.model(m)
    params = cifg_params(ctx.seed, m, ctx.devices[0])
    params0 = jax.device_get(params)
    data = traffic.population(mix, ctx.seed, m["vocab"], cl["seq_len"])
    n_base = data["counts"].shape[0]
    cohorts: List[np.ndarray] = []

    class FeedStore(ReplicatedPopulationStore):
        """The program's replicated store over the benchmark's base users;
        it notes the ids of each cohort the engine stages, in slot order."""

        def gather(self, ids):
            cohorts.append(np.array(ids, np.int64))
            return super().gather(ids)

    base = InMemoryPopulationStore(data["examples"], data["counts"],
                                   np.zeros(n_base, bool))
    store = FeedStore(base, sz["population"])
    dp = DPConfig(clip_norm=dpc["clip_norm"],
                  noise_multiplier=dpc["noise_multiplier"],
                  clients_per_round=sz["cohort"],
                  population=sz["population"],
                  server_opt=dpc["server_opt"], server_lr=dpc["server_lr"],
                  server_momentum=dpc["server_momentum"],
                  nesterov=dpc["nesterov"])
    client = ClientConfig(local_epochs=cl["local_epochs"],
                          batch_size=cl["batch_size"], lr=cl["lr"],
                          max_examples_per_user=cl["max_examples_per_user"])
    mesh = mix["mesh"]
    engine = SimEngine(model, store, dp, client,
                       n_local_batches=int(mix["local_batches"]),
                       availability=float(mix["availability"]),
                       population_backend="streamed", sampler="sharded",
                       num_pods=int(mesh["pods"]),
                       num_shards=int(mesh["shards"]))
    if alter is not None:
        alter(engine)
    state = engine.init_state(params, seed=_engine_seed(ctx.seed))
    losses, first_m = [], None
    for r in range(int(mix["checked_rounds"])):
        state, hist = engine.run(state, 1)
        losses.append(float(hist["loss"][0]))
        if r == 0:
            first_m = jax.device_get(state.opt_state.momentum)
    params_n = jax.device_get(state.params)
    return Setup(engine, state, params0, losses, first_m, params_n, cohorts,
                 data, sz["cohort"], sz["population"])


def _engine_seed(seed: int) -> int:
    return int(seed) % (1 << 32)


def reference(ctx: Context, s: Setup, precision: str = "f32",
              fault: str = "") -> Dict:
    """The plain reference's (or, at a lower precision or with a planted
    fault, the control's) run of the checked rounds on the same cohorts.
    ``fault`` is ``""`` or ``"half_batch"``: each round's mean taken over
    the first half of the cohort, the noise calibrated to that half."""
    cfg, mix = ctx.config, ctx.mix
    dpc, cl = cfg["dp"], cfg["client"]
    n_rounds = int(mix["checked_rounds"])
    examples, counts = s.data["examples"], s.data["counts"]
    n_base = counts.shape[0]
    cohort = s.cohort // 2 if fault == "half_batch" else s.cohort
    with jax.default_matmul_precision("highest"):
        losses, first_m, params_n = ref.fedavg_rounds(
            s.params0, s.cohorts[:n_rounds], cohort,
            lambda ids: examples[np.asarray(ids) % n_base],
            lambda ids: counts[np.asarray(ids) % n_base],
            _engine_seed(ctx.seed), vocab=cfg["model"]["vocab"],
            lr=cl["lr"], clip=dpc["clip_norm"],
            noise_multiplier=dpc["noise_multiplier"],
            server_lr=dpc["server_lr"], momentum=dpc["server_momentum"],
            local_batches=int(mix["local_batches"]),
            batch_size=cl["batch_size"], precision=precision,
            block=int(mix["reference_block"]), devices=ctx.devices)
    return {"losses": losses, "first_m": first_m, "params_n": params_n}


def readings(s: Setup, prog: Dict, ref_run: Dict) -> Dict[str, float]:
    """The numbers read: ``prog`` is the program's (or a control's) run of
    the checked rounds, ``ref_run`` the reference's. A run compares those
    that the configuration gives a limit."""
    leaves = compare.counted_leaves(ref_run["first_m"])
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref_run["losses"]))
    grad_gap, _ = compare.worst_norm_gap(prog["first_m"], ref_run["first_m"],
                                         leaves)
    change_gap, _ = compare.worst_norm_gap(
        compare.tree_sub(prog["params_n"], s.params0),
        compare.tree_sub(ref_run["params_n"], s.params0), leaves)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap}


def program_run(s: Setup) -> Dict:
    return {"losses": s.losses, "first_m": s.first_m,
            "params_n": s.params_n}


def window_tokens(ctx: Context, s: Setup, first: int, n: int) -> int:
    """Tokens the window's ``n`` rounds trained, from round ``first`` on:
    the label positions that are not PAD in the examples each slot of the
    staged cohorts draws, by the DP mechanism's key schedule."""
    cl, mix = ctx.config["client"], ctx.mix
    need = int(mix["local_batches"]) * int(cl["batch_size"])
    examples, counts = s.data["examples"], s.data["counts"]
    n_base = counts.shape[0]
    keys = ref.round_keys(_engine_seed(ctx.seed), first + n)
    total = 0
    for r in range(first, first + n):
        ids = s.cohorts[r] % n_base       # slot keys split over all slots
        idx = ref.example_indices(keys[r][0], counts[ids], need)[:s.cohort]
        total += flops.trained_tokens(np.take_along_axis(
            examples[ids[:s.cohort]], idx[:, :, None], axis=1))
    return total


def cohort_faults(s: Setup, n_rounds: int) -> int:
    """Rounds whose staged cohort is not ``cohort`` distinct users of the
    population."""
    bad = 0
    for ids in s.cohorts[:n_rounds]:
        real = ids[:s.cohort]
        if (len(np.unique(real)) != s.cohort or real.min() < 0
                or real.max() >= s.population):
            bad += 1
    return bad


def run(ctx: Context, alter=None) -> Result:
    cfg, mix = ctx.config, ctx.mix
    m, cl = cfg["model"], cfg["client"]
    s = setup(ctx, alter)
    setup_s = ctx.setup_seconds()
    block = int(mix["rounds_per_call"])
    gc.collect()
    compiles0 = ctx.compiles.programs
    holder: Dict = {}
    rounds, committed = 0, 0
    state = s.state
    with ctx.traced(holder):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("sim.run"):
                state, hist = s.engine.run(state, block)
            rounds += block
            ok = np.isfinite(hist["loss"]) & hist.get("committed", True)
            committed += int(np.sum(ok))
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        window = time.perf_counter() - t0
    compiles = ctx.compiles.programs - compiles0
    peak = memory_peak_bytes(ctx.devices)
    s.engine = s.state = state = None
    gc.collect()

    n_rounds = int(mix["checked_rounds"])
    ref_run = reference(ctx, s)
    checks = Checks()
    lim = cfg["limits"]
    for name, value in readings(s, program_run(s), ref_run).items():
        if name in lim:
            checks.add(name, value, lim[name])
    checks.add("bad_cohorts", cohort_faults(s, n_rounds), 0)

    positions = flops.fl_round_tokens(s.cohort, int(mix["local_batches"]),
                                      cl["batch_size"], cl["seq_len"])
    trained = window_tokens(ctx, s, n_rounds, rounds)
    record = {
        "kind": "fl", "rounds": rounds, "window_s": window,
        "chips": len(ctx.devices), "device_kind": ctx.devices[0].device_kind,
        "cohort": s.cohort, "local_batches": int(mix["local_batches"]),
        "batch_size": cl["batch_size"], "seq_len": cl["seq_len"],
        "positions_per_round": positions, "trained_tokens": trained,
        "embed_dim": m["embed_dim"],
        "hidden": m["hidden"], "vocab": m["vocab"],
        "n_params": sum(int(np.size(v))
                        for v in compare.flat(s.params0).values()),
        "compiles_in_window": compiles, "trace": holder.get("trace"),
    }
    if holder.get("trace") is not None:
        record["rounds_traced"] = rounds
    return Result(
        end_to_end={"train_rounds_per_s": committed / window,
                    "setup_s": setup_s},
        record=record, checks=checks, attempted=rounds,
        failed=rounds - committed, memory_peak_bytes=peak,
        trace=holder.get("trace"),
        notes=[f"fl: set-up {setup_s:.3f} s",
               f"fl: {rounds} rounds in {window:.3f} s, compiles in window "
               f"{compiles}, cohort {s.cohort} of {s.population}",
               f"fl: {trained} trained tokens in {rounds} rounds of "
               f"{positions} positions"])

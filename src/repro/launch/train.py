"""Federated training driver (end-to-end, CPU-scale simulation).

    PYTHONPATH=src python -m repro.launch.train --arch gboard-cifg-lstm \
        --rounds 100 --clients-per-round 40 --noise-multiplier 0.3

Runs Algorithm 1 on a simulated device population (availability gating +
Pace Steering), tracks the RDP accountant, optionally injects secret-sharing
canary devices, and checkpoints the server model.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import numpy as np

from repro.configs import ClientConfig, DPConfig, get_config
from repro.core.secret_sharer import make_canaries
from repro.data.corpus import BigramCorpus
from repro.data.federated import FederatedDataset
from repro.data.population_store import MmapPopulationStore
from repro.fl.faults import FaultConfig
from repro.fl.round import FederatedTrainer
from repro.models import build
from repro.train import checkpoint
from repro.utils.compile_cache import enable_compile_cache


def main(argv=None):
    """Parse ``argv`` (default: ``sys.argv[1:]``), train, checkpoint; returns
    the :class:`FederatedTrainer` so in-process callers can read its
    history."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gboard-cifg-lstm")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale variant of the arch")
    ap.add_argument("--vocab", type=int, default=None,
                    help="override the lstm vocab (default: the config's)")
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--n-users", type=int, default=300)
    ap.add_argument("--clients-per-round", type=int, default=40)
    ap.add_argument("--noise-multiplier", type=float, default=0.3)
    ap.add_argument("--clip-norm", type=float, default=0.8)
    ap.add_argument("--server-opt", default="momentum",
                    choices=["sgd", "momentum", "adam"])
    ap.add_argument("--server-lr", type=float, default=0.5)
    ap.add_argument("--server-momentum", type=float, default=0.9)
    ap.add_argument("--client-lr", type=float, default=0.3)
    ap.add_argument("--client-batch", type=int, default=10)
    ap.add_argument("--local-epochs", type=int, default=1)
    ap.add_argument("--inject-canaries", action="store_true")
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--out", default="experiments/runs")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--backend", default="engine",
                    choices=["engine", "engine_python", "host"],
                    help="engine = compiled multi-round simulator "
                         "(repro.fl.engine); host = numpy reference loop")
    ap.add_argument("--rounds-per-call", type=int, default=10,
                    help="rounds fused per jit call (engine backend)")
    ap.add_argument("--num-shards", type=int, default=1,
                    help="shard the per-round cohort axis across this many "
                         "devices per pod (engine backend; on CPU force "
                         "devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    ap.add_argument("--num-pods", type=int, default=1,
                    help="lay the cohort shards out over this many pods — "
                         "the 2-D (pod, data) batch slice of the production "
                         "mesh; needs num_pods x num_shards visible devices "
                         "(engine backend)")
    ap.add_argument("--cohort-chunk", type=int, default=None,
                    help="stream the round sum this many clients at a time "
                         "(peak update memory is O(chunk); default: auto — "
                         "largest divisor of the canonical block size ≤ 32; "
                         "0 = legacy materializing path)")
    ap.add_argument("--clip-path", default="fused",
                    choices=["fused", "tree"],
                    help="per-client clip→accumulate implementation: fused "
                         "Pallas dp_clip kernels (interpret mode on CPU, "
                         "compiled on TPU) or the pytree reference")
    ap.add_argument("--cell-path", default=None,
                    choices=["auto", "fused", "seq", "ref"],
                    help="lstm recurrence implementation: time-fused "
                         "sequence op with the Pallas cifg_cell kernel "
                         "(fused) or the jnp cell (seq), plain autodiff "
                         "scan (ref), or auto = fused on TPU / seq "
                         "elsewhere (default: the config's cell_path)")
    ap.add_argument("--population-backend", default=None,
                    choices=["device", "streamed"],
                    help="device = whole padded corpus resident on device "
                         "(simulation default); streamed = corpus stays on "
                         "the host and one cohort is staged per round with "
                         "double-buffered prefetch (engine backend; "
                         "bit-exact vs device)")
    ap.add_argument("--population-store", default=None, metavar="DIR",
                    help="path to an on-disk population store directory "
                         "(see tools/build_corpus.py); replaces the "
                         "synthesized FederatedDataset and implies "
                         "--population-backend streamed unless overridden")
    ap.add_argument("--sampler", default="global",
                    choices=["global", "sharded"],
                    help="cohort-selection implementation (engine backend): "
                         "global = monolithic O(N) sampler on one device "
                         "(the historical trajectory family); sharded = "
                         "mesh-sharded block-local Gumbel top-k "
                         "(fl.pop_sampler) — O(N) population state and "
                         "selection work shard over (pod, data), use at "
                         "fleet scale")
    ap.add_argument("--availability", type=float, default=0.3,
                    help="per-round device check-in probability; keep "
                         "availability·n_users above clients_per_round")
    ap.add_argument("--fault-dropout", type=float, default=0.0,
                    help="per-selected-client dropout probability (accepts "
                         "the task, never reports); any fault flag > 0 "
                         "enables the over-selection/report-goal round "
                         "protocol (engine backend)")
    ap.add_argument("--fault-straggler", type=float, default=0.0,
                    help="fraction of selected clients whose report latency "
                         "is Exponential(--fault-straggler-delay)")
    ap.add_argument("--fault-straggler-delay", type=float, default=1.0,
                    help="mean straggler report latency (same units as "
                         "--fault-deadline)")
    ap.add_argument("--fault-deadline", type=float, default=3.0,
                    help="round deadline; straggler reports past it are "
                         "dropped from the round")
    ap.add_argument("--fault-corrupt", type=float, default=0.0,
                    help="probability a delivered report is non-finite "
                         "garbage (rejected by the server-side guard)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed of the fault stream (disjoint from --seed's "
                         "training PRNG chain)")
    ap.add_argument("--report-goal", type=int, default=None,
                    help="minimum usable reports for a round to commit; "
                         "rounds below it abort (no server step, no privacy "
                         "spend). Default: ceil(0.8 x clients_per_round) "
                         "when faults are on")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="persist durable run state every N rounds (engine "
                         "backend); 0 = only the final checkpoint")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the run-state snapshot in --out if "
                         "one exists; the finished run is bit-identical to "
                         "an uninterrupted one")
    ap.add_argument("--crash-after", type=int, default=None,
                    help="simulate a crash: exit (skipping the final "
                         "checkpoint) once this many rounds are done — for "
                         "exercising --resume")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "lstm" and args.vocab is not None:
        cfg = cfg.with_(vocab=args.vocab)
    if args.cell_path is not None:
        cfg = cfg.with_(cell_path=args.cell_path)
    model = build(cfg)

    store = None
    if args.population_store is not None:
        if args.inject_canaries:
            raise SystemExit("--inject-canaries builds synthetic devices "
                             "into a FederatedDataset; bake them into the "
                             "store instead (tools/build_corpus.py "
                             "--inject-canaries)")
        store = MmapPopulationStore(args.population_store)
        ds = None
        n_users = store.n_users
        synth_ids = np.nonzero(np.asarray(store.synthetic))[0].tolist()
        print(f"population store: {args.population_store} "
              f"({n_users} users, E_max={store.emax}, "
              f"seq_len={store.row_len - 1}, {len(synth_ids)} synthetic)")
    else:
        corpus = BigramCorpus(vocab_size=cfg.vocab, seed=args.seed)
        ds = FederatedDataset(corpus, n_users=args.n_users,
                              seq_len=args.seq_len, sentences_per_user=30)
        canaries = []
        if args.inject_canaries:
            canaries = make_canaries(jax.random.PRNGKey(42), vocab=cfg.vocab)
            ds.inject_canaries(canaries)
            print(f"injected {len(canaries)} canaries "
                  f"({sum(c.n_u for c in canaries)} synthetic devices)")
        n_users = len(ds.users)
        synth_ids = [u.user_id for u in ds.users if u.is_synthetic]

    dp = DPConfig(clients_per_round=args.clients_per_round,
                  noise_multiplier=args.noise_multiplier,
                  clip_norm=args.clip_norm, server_opt=args.server_opt,
                  server_lr=args.server_lr,
                  server_momentum=args.server_momentum)
    cl = ClientConfig(local_epochs=args.local_epochs,
                      batch_size=args.client_batch, lr=args.client_lr)
    population_backend = args.population_backend or (
        "streamed" if store is not None else "device")
    if population_backend == "streamed" and args.backend == "host":
        raise SystemExit("--population-backend streamed needs the engine "
                         "backend (the host loop reads the dataset directly)")
    if args.sampler != "global" and args.backend == "host":
        raise SystemExit("--sampler sharded needs the engine backend (the "
                         "host loop samples via PopulationSim)")
    faults = None
    if (args.fault_dropout > 0 or args.fault_straggler > 0
            or args.fault_corrupt > 0 or args.report_goal is not None):
        faults = FaultConfig(seed=args.fault_seed,
                             dropout_prob=args.fault_dropout,
                             straggler_prob=args.fault_straggler,
                             straggler_mean_delay=args.fault_straggler_delay,
                             round_deadline=args.fault_deadline,
                             corrupt_prob=args.fault_corrupt,
                             report_goal=args.report_goal)
    if args.backend == "host" and (faults is not None or args.resume
                                   or args.checkpoint_every > 0
                                   or args.crash_after is not None):
        raise SystemExit("--fault-*/--report-goal/--checkpoint-every/"
                         "--resume/--crash-after need the engine backend "
                         "(the fault protocol and durable run state live in "
                         "the engine round bodies)")
    from repro.fl.population import PopulationSim
    pop = PopulationSim(n_users, availability=args.availability,
                        synthetic_ids=synth_ids, seed=args.seed)
    trainer = FederatedTrainer(model, ds, dp, cl, pop=pop, seed=args.seed,
                               n_local_batches=3, backend=args.backend,
                               rounds_per_call=args.rounds_per_call,
                               num_shards=args.num_shards,
                               num_pods=args.num_pods,
                               cohort_chunk=args.cohort_chunk,
                               clip_path=args.clip_path,
                               population_backend=population_backend,
                               population_store=store,
                               sampler=args.sampler,
                               fault_config=faults)

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log_every = max(1, args.rounds // 20)
    state_path = out / f"{args.arch}_r{args.rounds}_state.msgpack"
    done = 0
    if args.resume and state_path.exists():
        done = trainer.restore_run_state(state_path)
        print(f"resumed from {state_path} at round {done}")
    chunk = args.checkpoint_every if args.checkpoint_every > 0 \
        else args.rounds
    while done < args.rounds:
        k = min(chunk - done % chunk, args.rounds - done)
        if args.crash_after is not None:
            k = min(k, args.crash_after - done)
        trainer.train(k, log_every=log_every)
        done += k
        if args.checkpoint_every > 0 and done % args.checkpoint_every == 0 \
                and done < args.rounds:
            trainer.save_run_state(state_path)
        if args.crash_after is not None and done >= args.crash_after:
            print(f"simulated crash after round {done} "
                  f"(resume with --resume)")
            return trainer

    committed = sum(r.get("committed", True)
                    for r in trainer.state.history)
    eps = trainer.accountant.get_epsilon(1e-6)
    print(f"RDP accountant after {args.rounds} rounds "
          f"({committed} committed): eps={eps:.2f} at delta=1e-6 "
          f"(q={trainer.accountant.q:.4f})")

    ck = out / f"{args.arch}_r{args.rounds}.msgpack"
    checkpoint.save(ck, trainer.state.params,
                    meta={"arch": args.arch, "rounds": str(args.rounds),
                          "eps@1e-6": f"{eps:.3f}"})
    (out / f"{args.arch}_r{args.rounds}_history.json").write_text(
        json.dumps(trainer.state.history[-50:], indent=1))
    print(f"checkpoint: {ck}")
    return trainer


if __name__ == "__main__":
    main()

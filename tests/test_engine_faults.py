"""Production fault model: over-selection, report goals, DP-safe aborts,
crash-resumable training (`fl.faults` + the engine round protocol).

Contracts under test:

* faults *off* is the status quo: a zero-probability `FaultConfig` with
  ``report_goal == cohort`` is bit-identical to ``fault_config=None``;
* fault-on trajectories are deterministic in the fault seed and bit-exact
  across the {pods} × {shards} × {chunk} × {device, streamed} parity grid
  (fates are slot-level and replicated — where a slot computes is
  irrelevant);
* an aborted round (usable reports < report goal) leaves params/opt state
  bit-unchanged and spends no privacy budget; σ in committed rounds is
  calibrated to the report goal, never the realized survivor count;
* a run snapshotted mid-flight and restored replays to the bit-identical
  end state, faults on and off — including end-to-end through
  ``launch/train.py --crash-after/--resume`` (sha256-identical final
  checkpoint).

Shard/pod cases need forced devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_engine_faults.py
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ClientConfig, DPConfig, get_config
from repro.data.corpus import BigramCorpus
from repro.data.federated import FederatedDataset
from repro.data.population_store import InMemoryPopulationStore
from repro.fl.engine import SimEngine
from repro.fl.faults import FaultConfig, fault_fates
from repro.fl.round import FederatedTrainer
from repro.models import build

VOCAB = 300
ROUNDS = 3
COHORT = 32

# seed 3: a mixed stream — most rounds commit, corrupt slots appear
FAULTS = FaultConfig(seed=3, dropout_prob=0.3, straggler_prob=0.2,
                     straggler_mean_delay=2.0, round_deadline=3.0,
                     corrupt_prob=0.05)
# survival exactly 1/2 ⇒ sel_cohort 64, padded 64, chunk grid {1,2,4,8}
FAULTS_HALF = FaultConfig(seed=5, dropout_prob=0.5)

needs = {s: pytest.mark.skipif(
    len(jax.devices()) < s,
    reason=f"needs {s} devices (XLA_FLAGS="
           f"--xla_force_host_platform_device_count=8)") for s in (2, 4, 8)}


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gboard-cifg-lstm").with_(vocab=VOCAB, d_model=24,
                                               d_ff=48)
    model = build(cfg)
    corpus = BigramCorpus(vocab_size=VOCAB, seed=0)
    ds = FederatedDataset(corpus, n_users=80, seq_len=16,
                          sentences_per_user=20)
    return cfg, model, ds


@pytest.fixture(scope="module")
def runner(setup):
    """Memoized engine runs keyed by config (the parity grid shares one
    reference run per fault config)."""
    _, model, ds = setup
    data = ds.to_device_arrays()
    cache = {}

    def run(backend="device", *, faults="mixed", noise=0.3,
            sampling="fixed", chunk=None, num_shards=1, num_pods=1):
        key = (backend, faults, noise, sampling, chunk, num_shards,
               num_pods)
        if key not in cache:
            dp = DPConfig(clients_per_round=COHORT, noise_multiplier=noise,
                          clip_norm=0.8, server_opt="momentum",
                          server_lr=0.5, server_momentum=0.9,
                          sampling=sampling)
            cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
            fc = {"mixed": FAULTS, "half": FAULTS_HALF, "off": None,
                  "zero": FaultConfig(goal_frac=1.0),
                  "seed9": FaultConfig(seed=9, dropout_prob=0.3,
                                       straggler_prob=0.2,
                                       straggler_mean_delay=2.0,
                                       round_deadline=3.0,
                                       corrupt_prob=0.05)}[faults]
            src = (data if backend == "device"
                   else InMemoryPopulationStore.from_arrays(data))
            eng = SimEngine(
                model, src, dp, cl, n_local_batches=2,
                availability=1.0 if sampling == "poisson" else 0.6,
                rounds_per_call=ROUNDS, cohort_chunk=chunk,
                num_shards=num_shards, num_pods=num_pods,
                population_backend=backend, fault_config=fc)
            state = eng.init_state(model.init(jax.random.PRNGKey(1)),
                                   seed=0)
            state, hist = eng.run(state, ROUNDS)
            cache[key] = (eng, state, hist)
        return cache[key]

    return run


def _max_leaf_diff(a, b):
    d = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)
    return max(jax.tree_util.tree_leaves(d))


def _assert_bitwise(run_a, run_b, keys=("loss", "mean_update_norm",
                                        "n_clients", "noise_std")):
    _, sa, ha = run_a
    _, sb, hb = run_b
    for k in keys:
        if k in ha or k in hb:
            np.testing.assert_array_equal(np.asarray(ha[k]),
                                          np.asarray(hb[k]))
    np.testing.assert_array_equal(np.asarray(sa.participation),
                                  np.asarray(sb.participation))
    np.testing.assert_array_equal(np.asarray(sa.last_round),
                                  np.asarray(sb.last_round))
    np.testing.assert_array_equal(np.asarray(sa.key), np.asarray(sb.key))
    assert _max_leaf_diff(sa.params, sb.params) == 0.0
    assert _max_leaf_diff(sa.opt_state, sb.opt_state) == 0.0


FAULT_KEYS = ("loss", "mean_update_norm", "n_clients", "noise_std",
              "n_selected", "n_reported", "committed")


# ------------------------------------------------------- fates unit level

def test_fates_are_consistent_and_deterministic():
    cfg = FAULTS
    key = jax.random.PRNGKey(cfg.seed)
    f = fault_fates(key, 7, 256, cfg)
    g = fault_fates(key, 7, 256, cfg)
    for a, b in zip(f, g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    rep, cor, dro, late = (np.asarray(x) for x in f)
    assert not np.any(rep & dro) and not np.any(rep & late)
    assert not np.any(dro & late)          # a dropped slot never reports late
    assert np.all(rep | dro | late)        # fates partition the slots
    assert np.all(~cor | rep)              # corrupt ⇒ reported
    # a different round index is a different draw
    h = fault_fates(key, 8, 256, cfg)
    assert np.any(np.asarray(h.reported) != rep)


def test_fates_monotone_in_dropout():
    """Monotone coupling: same uniforms, higher threshold ⇒ the dropped set
    only grows. (`test_accountant.py` builds ε-monotonicity on this.)"""
    key = jax.random.PRNGKey(0)
    prev = np.zeros(512, bool)
    for p in (0.1, 0.3, 0.6, 0.9):
        cur = np.asarray(fault_fates(key, 0, 512,
                                     FaultConfig(dropout_prob=p)).dropped)
        assert np.all(prev <= cur)
        prev = cur


def test_fault_config_validation():
    with pytest.raises(ValueError):
        FaultConfig(dropout_prob=1.0)
    with pytest.raises(ValueError):
        FaultConfig(straggler_mean_delay=0.0)
    with pytest.raises(ValueError):
        FaultConfig(goal_frac=0.0)
    with pytest.raises(ValueError):
        FaultConfig(report_goal=0)
    fc = FaultConfig(dropout_prob=0.5)
    assert fc.over_selection(32) == 64
    assert fc.resolve_report_goal(32) == 26          # ceil(0.8·32)
    assert FaultConfig(report_goal=30).resolve_report_goal(32) == 30
    assert FaultConfig(dropout_prob=0.5,
                       over_select=False).over_selection(32) == 32


# ---------------------------------------------------- faults-off invariance

def test_zero_prob_config_is_bit_identical_to_none(runner):
    """FaultConfig(0 probs, report_goal == cohort) traces the fault branch
    but must reproduce the fault-free trajectory bit-for-bit."""
    _assert_bitwise(runner(faults="off"), runner(faults="zero"))


# ------------------------------------------------- determinism in the seed

def test_fault_seed_determinism(runner, setup):
    _, model, ds = setup
    data = ds.to_device_arrays()
    dp = DPConfig(clients_per_round=COHORT, noise_multiplier=0.3,
                  clip_norm=0.8, server_opt="momentum", server_lr=0.5,
                  server_momentum=0.9)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    eng = SimEngine(model, data, dp, cl, n_local_batches=2,
                    availability=0.6, rounds_per_call=ROUNDS,
                    fault_config=FAULTS)
    state = eng.init_state(model.init(jax.random.PRNGKey(1)), seed=0)
    state, hist = eng.run(state, ROUNDS)
    _assert_bitwise(runner(faults="mixed"), (eng, state, hist),
                    keys=FAULT_KEYS)
    # ... and a different fault seed gives a different trajectory
    _, _, h9 = runner(faults="seed9")
    ref = runner(faults="mixed")[2]
    assert np.any(np.asarray(h9["n_reported"])
                  != np.asarray(ref["n_reported"]))


# --------------------------------------------------- fault-on parity grid

def test_fault_parity_streamed(runner):
    _assert_bitwise(runner("device"), runner("streamed"), keys=FAULT_KEYS)


def test_fault_parity_poisson_streamed(runner):
    _assert_bitwise(runner("device", sampling="poisson"),
                    runner("streamed", sampling="poisson"),
                    keys=FAULT_KEYS)


@pytest.mark.parametrize("chunk", [1, 2])
def test_fault_parity_chunk(runner, chunk):
    _assert_bitwise(runner("device", faults="half"),
                    runner("device", faults="half", chunk=chunk),
                    keys=FAULT_KEYS)


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [4, 8])
def test_fault_parity_chunk_wide(runner, chunk):
    _assert_bitwise(runner("device", faults="half"),
                    runner("device", faults="half", chunk=chunk),
                    keys=FAULT_KEYS)


@needs[2]
def test_fault_parity_sharded(runner):
    _assert_bitwise(runner("device"), runner("device", num_shards=2),
                    keys=FAULT_KEYS)


@needs[4]
def test_fault_parity_pods(runner):
    _assert_bitwise(runner("device"),
                    runner("device", num_pods=2, num_shards=2),
                    keys=FAULT_KEYS)


@needs[4]
def test_fault_parity_pods_streamed(runner):
    _assert_bitwise(runner("device"),
                    runner("streamed", num_pods=2, num_shards=2),
                    keys=FAULT_KEYS)


@pytest.mark.slow
@needs[8]
def test_fault_parity_pods_wide(runner):
    _assert_bitwise(runner("device"),
                    runner("device", num_pods=2, num_shards=4),
                    keys=FAULT_KEYS)


# ------------------------------------------------ protocol-level semantics

def test_over_selection_sizing(runner):
    eng, _, hist = runner(faults="mixed")
    assert eng.sel_cohort == FAULTS.over_selection(COHORT)
    assert eng.report_goal == FAULTS.resolve_report_goal(COHORT)
    assert np.all(np.asarray(hist["n_selected"]) == eng.sel_cohort)
    # survivors: reported ≥ accepted, selected ≥ reported
    assert np.all(np.asarray(hist["n_reported"])
                  <= np.asarray(hist["n_selected"]))
    assert np.all(np.asarray(hist["n_clients"])
                  <= np.asarray(hist["n_reported"]))


def test_sigma_calibrated_to_report_goal(runner):
    """σ = zS / report_goal in every round — committed or not, whatever the
    realized survivor count."""
    eng, _, hist = runner(faults="mixed")
    expect = np.float32(0.3 * 0.8 / np.float32(eng.report_goal))
    np.testing.assert_array_equal(np.asarray(hist["noise_std"]),
                                  np.full(ROUNDS, expect))


def test_commit_iff_goal_met(runner):
    eng, _, hist = runner(faults="mixed")
    np.testing.assert_array_equal(
        np.asarray(hist["committed"]),
        np.asarray(hist["n_clients"]) >= eng.report_goal)
    assert np.all(np.isfinite(np.asarray(hist["loss"])))


def _trainer(setup, fc, **kw):
    _, model, ds = setup
    dp = DPConfig(clients_per_round=8, noise_multiplier=0.3, clip_norm=0.8,
                  server_opt="momentum", server_lr=0.5, server_momentum=0.9)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    kw.setdefault("backend", "engine")
    return FederatedTrainer(model, ds, dp, cl, seed=0, n_local_batches=2,
                            rounds_per_call=4, fault_config=fc, **kw)


def test_abort_leaves_state_bit_unchanged(setup):
    """dropout 0.9 with no over-selection and goal == cohort: every round
    misses the goal ⇒ params/opt never move, accountant never steps."""
    fc = FaultConfig(seed=1, dropout_prob=0.9, over_select=False,
                     report_goal=8)
    tr = _trainer(setup, fc)
    before = jax.device_get(tr._estate)
    tr.train(3)
    after = tr._estate
    assert not any(r["committed"] for r in tr.state.history)
    assert _max_leaf_diff(before.params, after.params) == 0.0
    assert _max_leaf_diff(before.opt_state, after.opt_state) == 0.0
    assert tr.accountant.rounds == 0
    # the PRNG chain still advanced: aborts don't replay sampling
    assert np.any(np.asarray(before.key) != np.asarray(after.key))


def test_trainer_accounts_committed_rounds_only(setup):
    tr = _trainer(setup, FAULTS)
    tr.train(6)
    committed = sum(r["committed"] for r in tr.state.history)
    assert tr.accountant.rounds == committed
    # corrupt rejection shows up as accepted < reported in some round
    assert all(r["n_clients"] <= r["n_reported"]
               for r in tr.state.history)


def test_host_backend_rejects_fault_config(setup):
    with pytest.raises(ValueError, match="engine-backend"):
        _trainer(setup, FAULTS, backend="host")


def test_materializing_path_rejects_fault_config(setup):
    with pytest.raises(ValueError, match="cohort_chunk"):
        _trainer(setup, FAULTS, cohort_chunk=0)


# ----------------------------------------------------- crash-resume parity

@pytest.mark.parametrize("fc", [None, FAULTS],
                         ids=["faults-off", "faults-on"])
def test_save_restore_resumes_bit_exact(setup, tmp_path, fc):
    ref = _trainer(setup, fc)
    ref.train(8)
    a = _trainer(setup, fc)
    a.train(5)
    a.save_run_state(tmp_path / "state.msgpack")
    b = _trainer(setup, fc)
    done = b.restore_run_state(tmp_path / "state.msgpack")
    assert done == 5
    b.train(8 - done)
    assert _max_leaf_diff(ref.state.params, b.state.params) == 0.0
    assert _max_leaf_diff(ref.state.opt_state, b.state.opt_state) == 0.0
    assert ref.state.history == b.state.history
    assert ref.accountant.rounds == b.accountant.rounds
    np.testing.assert_array_equal(ref.participation, b.participation)


def test_restore_rejects_wrong_kind(setup, tmp_path):
    from repro.train import checkpoint
    tr = _trainer(setup, None)
    checkpoint.save(tmp_path / "model.msgpack", tr.state.params,
                    meta={"kind": "model"})
    with pytest.raises(checkpoint.CheckpointError, match="run-state"):
        tr.restore_run_state(tmp_path / "model.msgpack")


def _cli(tmp_path, extra):
    from repro.launch import train as train_cli
    argv = ["--reduced", "--vocab", "120", "--rounds", "6",
            "--n-users", "40", "--clients-per-round", "8",
            "--noise-multiplier", "0.3", "--availability", "0.6",
            "--rounds-per-call", "2", "--seed", "0",
            "--out", str(tmp_path)] + extra
    train_cli.main(argv)
    ck = tmp_path / "gboard-cifg-lstm_r6.msgpack"
    return hashlib.sha256(ck.read_bytes()).hexdigest() if ck.exists() \
        else None


@pytest.mark.slow
@pytest.mark.parametrize("fault_args", [[], ["--fault-dropout", "0.3",
                                             "--fault-corrupt", "0.05"]],
                         ids=["faults-off", "faults-on"])
def test_cli_crash_resume_sha256_identical(tmp_path, fault_args):
    """launch/train.py killed after round 3 and restarted with --resume
    produces a byte-identical final checkpoint."""
    ref = _cli(tmp_path / "ref", fault_args)
    assert ref is not None
    crashed = _cli(tmp_path / "res", fault_args
                   + ["--checkpoint-every", "2", "--crash-after", "3"])
    assert crashed is None          # crashed before the final checkpoint
    resumed = _cli(tmp_path / "res", fault_args
                   + ["--checkpoint-every", "2", "--resume"])
    assert resumed == ref

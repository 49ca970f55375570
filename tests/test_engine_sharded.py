"""Cohort-sharded engine ↔ unsharded engine parity (the DP-invariant core).

The sharded engine (`SimEngine(num_shards=S)`) must be *the same mechanism*
as the unsharded one, not an approximation: same PRNG stream → identical
cohorts, and — because the clipped sum goes through the canonical block-tree
reduction (`engine.cohort_sum` association) — bit-identical trajectories
for every shard count dividing `engine.CANON_BLOCKS`. That bitwise
invariance is what keeps the clipped-sum sensitivity bound S/(qN), and
hence the accountant's ε, independent of the aggregation topology.

Shard counts above the visible device count are skipped; run the full
{1, 2, 4, 8} grid on CPU with

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m pytest -q tests/test_engine_sharded.py

(the CI ``tier1-sharded`` matrix leg does exactly this).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ClientConfig, DPConfig, get_config
from repro.data.corpus import BigramCorpus
from repro.data.federated import FederatedDataset
from repro.fl.engine import CANON_BLOCKS, SimEngine, canon_pad
from repro.fl.population import PopulationSim
from repro.fl.round import FederatedTrainer
from repro.models import build

VOCAB = 300
ROUNDS = 5
SHARDS = (2, 4, 8)

needs = {s: pytest.mark.skipif(
    len(jax.devices()) < s,
    reason=f"needs {s} devices (XLA_FLAGS="
           f"--xla_force_host_platform_device_count=8)") for s in SHARDS}
shard_params = [pytest.param(s, marks=needs[s]) for s in SHARDS]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("gboard-cifg-lstm").with_(vocab=VOCAB, d_model=24,
                                               d_ff=48)
    model = build(cfg)
    corpus = BigramCorpus(vocab_size=VOCAB, seed=0)
    ds = FederatedDataset(corpus, n_users=80, seq_len=16,
                          sentences_per_user=20)
    return cfg, model, ds


def _run(model, ds, *, num_shards=1, sampling="fixed", noise=0.0,
         cohort=12, rounds=ROUNDS, rounds_per_call=3):
    dp = DPConfig(clients_per_round=cohort, noise_multiplier=noise,
                  clip_norm=0.8, server_opt="momentum", server_lr=0.5,
                  server_momentum=0.9, sampling=sampling)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    eng = SimEngine(model, ds.to_device_arrays(), dp, cl, n_local_batches=2,
                    availability=1.0 if sampling == "poisson" else 0.5,
                    rounds_per_call=rounds_per_call, num_shards=num_shards)
    state = eng.init_state(model.init(jax.random.PRNGKey(1)), seed=0)
    state, hist = eng.run(state, rounds)
    return eng, state, hist


def _max_leaf_diff(a, b):
    d = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x.astype(jnp.float32)
                                           - y.astype(jnp.float32)))), a, b)
    return max(jax.tree_util.tree_leaves(d))


@pytest.fixture(scope="module")
def baselines(setup):
    """num_shards=1 reference runs, one per (sampling, noise) config."""
    _, model, ds = setup
    return {key: _run(model, ds, sampling=key[0], noise=key[1])
            for key in (("fixed", 0.0), ("poisson", 0.0), ("fixed", 0.3))}


@pytest.mark.parametrize("num_shards", shard_params)
@pytest.mark.parametrize("sampling", ["fixed", "poisson"])
def test_sharded_trajectory_parity_bit_exact(setup, baselines, sampling,
                                             num_shards):
    """Zero noise: sharding the cohort axis must not move a single bit —
    identical cohorts (participation), identical realized round sizes, and
    bit-exact params/history against the unsharded engine."""
    _, model, ds = setup
    ref_eng, ref_state, ref_hist = baselines[(sampling, 0.0)]
    eng, state, hist = _run(model, ds, num_shards=num_shards,
                            sampling=sampling)
    assert eng.padded == ref_eng.padded  # same canonical grid, no truncation
    np.testing.assert_array_equal(np.asarray(state.participation),
                                  np.asarray(ref_state.participation))
    np.testing.assert_array_equal(hist["n_clients"], ref_hist["n_clients"])
    np.testing.assert_array_equal(hist["loss"], ref_hist["loss"])
    np.testing.assert_array_equal(hist["mean_update_norm"],
                                  ref_hist["mean_update_norm"])
    assert _max_leaf_diff(state.params, ref_state.params) == 0.0
    assert _max_leaf_diff(state.opt_state, ref_state.opt_state) == 0.0


@pytest.mark.parametrize("num_shards", [pytest.param(8, marks=needs[8])])
def test_sharded_parity_survives_noise(setup, baselines, num_shards):
    """σ > 0: the Gaussian draw comes from the *replicated* PRNG stream
    (drawn once, after the global sum), so even noised trajectories are
    bit-identical across shard counts — σ calibration can't drift with the
    topology."""
    _, model, ds = setup
    _, ref_state, ref_hist = baselines[("fixed", 0.3)]
    _, state, hist = _run(model, ds, num_shards=num_shards, noise=0.3)
    np.testing.assert_array_equal(hist["loss"], ref_hist["loss"])
    np.testing.assert_allclose(hist["noise_std"], 0.3 * 0.8 / 12, rtol=1e-6)
    assert _max_leaf_diff(state.params, ref_state.params) == 0.0
    np.testing.assert_array_equal(np.asarray(state.participation),
                                  np.asarray(ref_state.participation))


@pytest.mark.parametrize("num_shards", [pytest.param(4, marks=needs[4])])
def test_ragged_cohort_pads_not_truncates(setup, num_shards):
    """Regression: cohort=10 doesn't divide 4 shards (or the canonical
    8-block grid) — the buffer must pad to the next canonical multiple and
    keep *all* 10 devices in the round, never drop the remainder."""
    _, model, ds = setup
    eng, state, hist = _run(model, ds, num_shards=num_shards, cohort=10,
                            rounds=3)
    assert eng.padded == canon_pad(10, num_shards) == 16
    assert eng.padded % num_shards == 0
    np.testing.assert_array_equal(hist["n_clients"], 10)  # nobody truncated
    assert int(np.asarray(state.participation).sum()) == 3 * 10
    # padded slots are masked out of the population vectors: only sampled
    # devices have a last_round stamp
    stamped = np.asarray(state.last_round) >= 0
    assert stamped.sum() == np.count_nonzero(np.asarray(state.participation))
    # and the ragged cohort still matches the unsharded engine bitwise
    _, ref_state, ref_hist = _run(model, ds, cohort=10, rounds=3)
    np.testing.assert_array_equal(hist["loss"], ref_hist["loss"])
    assert _max_leaf_diff(state.params, ref_state.params) == 0.0


def test_insufficient_devices_is_a_clear_error(setup):
    """num_shards beyond the visible device count must fail loudly at
    construction, naming the XLA_FLAGS escape hatch — not at first run."""
    _, model, ds = setup
    dp = DPConfig(clients_per_round=12, noise_multiplier=0.0, clip_norm=0.8)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    with pytest.raises(ValueError, match="xla_force_host_platform"):
        SimEngine(model, ds.to_device_arrays(), dp, cl,
                  num_shards=len(jax.devices()) + 1)


def test_trainer_num_shards_validation(setup):
    """The trainer forwards num_shards to the engine and rejects it on the
    host backend (which has no cohort axis to shard)."""
    _, model, ds = setup
    dp = DPConfig(clients_per_round=12, noise_multiplier=0.0, clip_norm=0.8)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    with pytest.raises(ValueError, match="engine"):
        FederatedTrainer(model, ds, dp, cl, backend="host", num_shards=2)


def test_model_axis_mesh_config_rejected(setup):
    """The engine shards the cohort over its batch axes only — a MeshConfig
    carrying the model-parallel axis (the full production mesh) must fail
    loudly, not be silently flattened into the cohort layout."""
    from repro.configs.base import MULTI_POD, SINGLE_POD
    _, model, ds = setup
    dp = DPConfig(clients_per_round=12, noise_multiplier=0.0, clip_norm=0.8)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    for cfg in (SINGLE_POD, MULTI_POD):
        with pytest.raises(ValueError, match="batch axes"):
            SimEngine(model, ds.to_device_arrays(), dp, cl, mesh_config=cfg)


@pytest.mark.parametrize("num_shards", [pytest.param(2, marks=needs[2])])
def test_trainer_sharded_matches_unsharded(setup, num_shards):
    """FederatedTrainer(backend="engine", num_shards=S) reproduces the
    unsharded trainer's history and participation exactly at zero noise."""
    _, model, ds = setup
    dp = DPConfig(clients_per_round=12, noise_multiplier=0.0, clip_norm=0.8,
                  server_opt="momentum", server_lr=0.5, server_momentum=0.9)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    runs = {}
    for s in (1, num_shards):
        pop = PopulationSim(len(ds.users), availability=0.6, seed=0)
        tr = FederatedTrainer(model, ds, dp, cl, pop=pop, n_local_batches=2,
                              seed=0, backend="engine", rounds_per_call=3,
                              num_shards=s)
        tr.train(4)
        runs[s] = tr
    a, b = runs[1], runs[num_shards]
    assert [r["loss"] for r in a.state.history] == \
        [r["loss"] for r in b.state.history]
    np.testing.assert_array_equal(a.participation, b.participation)
    assert a.accountant.rounds == b.accountant.rounds == 4


@pytest.mark.slow
@pytest.mark.parametrize("num_shards", [pytest.param(8, marks=needs[8])])
def test_sharded_scan_vs_python_loop(setup, num_shards):
    """The sharded round body is identical under the compiled scan and the
    per-round-jit reference loop (shard_map composes with both)."""
    _, model, ds = setup
    eng, sa, ha = _run(model, ds, num_shards=num_shards, noise=0.3)
    sb_init = eng.init_state(model.init(jax.random.PRNGKey(1)), seed=0)
    sb, hb = eng.run_python(sb_init, ROUNDS)
    np.testing.assert_array_equal(ha["loss"], hb["loss"])
    np.testing.assert_array_equal(np.asarray(sa.participation),
                                  np.asarray(sb.participation))
    assert _max_leaf_diff(sa.params, sb.params) == 0.0


@pytest.mark.slow
@pytest.mark.parametrize("num_shards", [pytest.param(8, marks=needs[8])])
def test_eval_hook_under_sharding(setup, num_shards):
    """In-scan eval hooks run on the replicated post-update params — their
    outputs must match the unsharded engine bitwise too."""
    _, model, ds = setup

    def eval_fn(params, round_idx):
        flat = jnp.concatenate([jnp.ravel(l) for l in
                                jax.tree_util.tree_leaves(params)])
        return {"pnorm": jnp.linalg.norm(flat)}

    dp = DPConfig(clients_per_round=12, noise_multiplier=0.3, clip_norm=0.8,
                  server_opt="momentum", server_lr=0.5, server_momentum=0.9)
    cl = ClientConfig(local_epochs=1, batch_size=10, lr=0.3)
    hists = {}
    for s in (1, num_shards):
        eng = SimEngine(model, ds.to_device_arrays(), dp, cl,
                        n_local_batches=2, availability=0.5,
                        rounds_per_call=2, num_shards=s,
                        eval_fn=eval_fn, eval_every=2)
        state = eng.init_state(model.init(jax.random.PRNGKey(1)), seed=0)
        _, hists[s] = eng.run(state, 4)
    np.testing.assert_array_equal(hists[1]["eval_mask"],
                                  hists[num_shards]["eval_mask"])
    np.testing.assert_array_equal(hists[1]["eval"]["pnorm"],
                                  hists[num_shards]["eval"]["pnorm"])


@pytest.mark.slow
def test_checkpoint_byte_parity_across_pods_and_shards(tmp_path):
    """End to end through the real CLI: `launch/train.py` runs with every
    {pods 1, 2} × {shards 1, 4} topology must write byte-identical
    checkpoints (sha256 over the .msgpack) — the strongest statement that
    the DP mechanism a launch ships is independent of the mesh it trained
    on."""
    import hashlib
    from repro.launch import train as train_cli

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=16)")

    digests = {}
    for pods, shards in ((1, 1), (1, 4), (2, 1), (2, 4)):
        out = tmp_path / f"p{pods}s{shards}"
        argv = ["--arch", "gboard-cifg-lstm", "--reduced",
                "--vocab", "64", "--rounds", "2", "--n-users", "40",
                "--clients-per-round", "8", "--noise-multiplier", "0.25",
                "--seq-len", "8", "--rounds-per-call", "2",
                "--num-pods", str(pods), "--num-shards", str(shards),
                "--seed", "0", "--out", str(out)]
        train_cli.main(argv)
        (ck,) = out.glob("*.msgpack")
        digests[(pods, shards)] = hashlib.sha256(ck.read_bytes()).hexdigest()
    assert len(set(digests.values())) == 1, digests


def test_canon_pad_grid():
    """The canonical grid is shard-count-invariant exactly where the parity
    suite claims it: every shard count dividing CANON_BLOCKS yields the
    same padded size (same reduction tree), and padding never shrinks."""
    for n in (1, 7, 8, 10, 12, 100, 1000):
        sizes = {canon_pad(n, s) for s in (1, 2, 4, 8)}
        assert len(sizes) == 1          # identical grid across the matrix
        (p,) = sizes
        assert p >= n and p % CANON_BLOCKS == 0
    assert canon_pad(12, 3) % 3 == 0    # non-canonical counts still align

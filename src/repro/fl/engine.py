"""Compiled multi-round DP-FedAvg simulation engine (cohort-sharded).

The host-loop trainer (`repro.fl.round.FederatedTrainer`, backend="host")
re-stacks client tensors with numpy and re-enters jit every round; at
thousands of simulated rounds (secret-sharer sweeps, Table 5/6/7/8
ablations) that host round-trip dominates wall clock. This engine keeps the
*entire* simulation on device and runs K federated rounds per jit call with
a single ``lax.scan``:

* **population** — per-round availability draws + Pace Steering weights
  computed on device from a ``last_round`` vector (the weight function is a
  hook, see :func:`pace_steering_weights`);
* **sampling** — fixed-size weighted sampling without replacement via
  ``jax.random.choice`` (Gumbel top-k under the hood, matching numpy's
  successive-draw semantics; zero-weight devices are never selected while
  ≥ cohort positive-weight devices exist);
* **data** — gather-based client batching from the padded device-resident
  corpus tensor built by ``FederatedDataset.to_device_arrays()``; no host
  data movement after engine construction;
* **round** — the clip → sum → noise → server-optimizer (Nesterov) step of
  Algorithm 1 fused into the scan body (`repro.fl.client` +
  `repro.core.dp_fedavg.finalize_round`), with state buffers donated across
  calls. The clipped sum is accumulated **streamingly**: inside each
  canonical block a ``lax.scan`` over contiguous ``cohort_chunk``-client
  chunks runs gather → local SGD → fused Pallas clip→accumulate
  (`kernels.dp_clip`) and folds straight into the block's running partial,
  so peak update memory is O(cohort_chunk·|params|) — not the materializing
  O(cohort·|params|) stack — and fully-masked padding chunks skip their
  compute via a scalar ``lax.cond``. The per-slot fold is strictly
  sequential (`fl.reduction.slot_fold` association), making trajectories
  bit-identical across every ``cohort_chunk`` dividing the block size;
* **eval hooks** — a user-supplied ``eval_fn(params, round_idx) -> pytree``
  evaluated *inside* the scan body every ``eval_every`` rounds (a masked
  ``lax.cond`` skips the computation on the other rounds), with stacked
  per-round outputs returned in the history next to the training metrics;
* **Poisson rounds** — ``sampling="poisson"`` draws each available device
  i.i.d. Bernoulli(q = qN/N) per round [MRTZ17]. Rounds are variable-size
  but shapes stay static: the first ``poisson_buffer`` selected devices fill
  a fixed-shape cohort buffer and a 0/1 slot mask is folded into the
  clipped sum; Δ̄ and σ keep the DPConfig calibration z·S/(qN) against the
  *expected* round size.

Cohort sharding (``num_shards > 1`` / ``num_pods > 1``)
-------------------------------------------------------

The per-round cohort axis shards across a 1-D ``data`` mesh — or, with
``num_pods > 1``, the 2-D ``(pod, data)`` batch slice of the multi-pod
production mesh — with ``shard_map`` (`sharding.specs.sim_mesh_config` /
`launch.mesh.make_cohort_mesh`): client batching and the per-client clip
live per-shard, and a single collective reduction produces the global
clipped sum before the (replicated) noise/Nesterov server step. Cohort
sampling and the Poisson draw stay replicated — every shard sees the same
PRNG stream, so all shards agree on the cohort and noise is drawn once
(σ calibration is untouched by the topology). Params and the noise stream
are pod-replicated (hybrid-FSDP layout of `sharding.specs`): only the
round-sum block partials ever cross the inter-pod axis.

Because float addition is not associative, a naive per-shard partial sum +
``psum`` would make params drift with the shard count. Instead the engine
reduces through a **canonical block tree** (:func:`cohort_sum`): the padded
cohort buffer is split into :data:`CANON_BLOCKS` contiguous blocks whose
boundaries align with every supported shard boundary, each block is summed
locally, and the block partials are combined by a fixed pairwise tree. On
the 1-D mesh the shards ``all_gather`` the partials so the tree is
evaluated identically everywhere; on the 2-D mesh the gather runs in two
stages — each pod's contiguous block group is gathered over the intra-pod
``data`` axis and folded *pod-locally*, and only those pod partials cross
the expensive ``pod`` axis, where the same pairwise tree combines them
(`reduction.fold_pods`). Since :data:`CANON_BLOCKS` is a power of two the
two-level fold is a re-bracketing of the flat tree, so the result is
*bit-identical for every ``(num_pods, num_shards)`` whose product divides*
:data:`CANON_BLOCKS` — `tests/test_engine_sharded.py` and
`tests/test_engine_pods.py` assert zero-noise bit-exact trajectory parity
across shards {1, 2, 4, 8} and pods {1, 2, 4} — which is exactly the
property the DP analysis needs: the clipped-sum sensitivity bound S/(qN)
survives unchanged under any aggregation topology [MRTZ17].

Cohort / buffer sizes that don't divide the shard count are **padded**
(masked empty slots), never truncated — dropping devices would silently
shrink the round and break the σ = zS/(qN) calibration.

`run` (compiled scan) and `run_python` (per-round jit, Python loop) execute
the *same* traced round body from the same PRNG stream, so they sample
identical cohorts and are numerically interchangeable — `tests/test_engine.py`
asserts trajectory parity and zero-noise bit-exactness.

Streamed population backend (``population_backend="streamed"``)
---------------------------------------------------------------

The default (``"device"``) backend holds the whole padded corpus tensor on
device — O(N·E_max·seq_len) device memory, a hard wall at 10⁶–10⁷ users.
The streamed backend keeps the corpus host-resident behind a
`data.population_store.PopulationStore` (RAM, mmap shards on disk, or an
O(1) replicated view) and stages exactly one cohort per round:

* the K-round ``lax.scan`` becomes a **host-driven round loop** around two
  jitted bodies compiled once each — ``_sample_body`` (availability draw,
  Pace-Steering/Poisson cohort selection, population-vector updates; only
  O(N)-*vector* state ever touches the device) and ``_compute_body`` (the
  gather → local SGD → clip → noise → server step, donated params/opt);
* after round k's cohort ids come back from the sampler (a tiny transfer),
  the host gathers their rows from the store and ``jax.device_put``s them
  into one of **two ping-ponged (padded, E_max, seq_len+1) cohort
  buffers** while round k−1's chunked compute scan is still in flight —
  the ``cohort_chunk`` streaming boundaries (PR 4) are what the transfer
  overlaps. Per-round device corpus residency is O(2·cohort·E_max),
  independent of N;
* the sampler chain (PRNG key, ``last_round``, ``participation``) is
  independent of the params chain, which is what makes the one-round
  lookahead legal: round k+1's cohort is fully determined before round k's
  server step lands.

Bit-exactness: the sampler consumes the identical PRNG splits as the fused
device round body, and the compute body draws per-slot example indices from
the *same* per-slot keys against the same ``counts[u]`` bounds — gathering
``examples[u]`` from a staged host buffer instead of the device-resident
corpus tensor selects bit-identical token rows, so streamed trajectories
are **bit-exact against the device backend** across the whole
{pods} × {shards} × {chunk} parity grid (`tests/test_engine_streamed.py`).

Production fault model (``fault_config=FaultConfig(...)``)
----------------------------------------------------------

With a `fl.faults.FaultConfig` the engine runs the deployed round protocol
instead of the perfect-fleet simulation (paper §III; 1710.06963 §B):

* **over-selection** — each round samples ``ceil(target /
  expected_survival)`` clients (fixed mode; Poisson scales q the same way)
  so the expected survivor count is the full target cohort;
* **per-slot fates** — a seeded stream disjoint from the training PRNG
  chain (`fl.faults.fault_fates`) marks slots dropped / late / corrupt;
  dropped and late slots are masked out of the round sum (exact ±0, the
  Poisson-exclusion machinery), corrupt slots get non-finite values
  injected into their *update* and are rejected by the server-side guard
  (`fl.client.chunk_accumulate(guard_nonfinite=True)`) — again exact ±0;
* **report goal / abort** — the round *commits* only if accepted survivors
  reach ``report_goal``; otherwise the server step is skipped via
  ``lax.cond`` (params/opt state bit-unchanged — the noise draw still
  consumes its key so the PRNG stream is fate-independent) and the trainer
  records no accountant step for it. σ **and** the released mean are
  calibrated to ``report_goal``, never the realized count, preserving the
  sensitivity bound S/report_goal whatever the fleet does.

``fault_config=None`` (the default) traces literally the fault-free round
program — fault-off trajectories are bit-identical to the engine before the
fault model existed. Fault-on trajectories are deterministic in the fault
seed and bit-exact across the whole {pods} × {shards} × {chunk} ×
{device, streamed} grid, because fates are slot-level and replicated
(`tests/test_engine_faults.py`). Faults require the streaming accumulation
path (``cohort_chunk > 0``) — the guard lives in the per-slot fold.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ClientConfig, DPConfig, MeshConfig
from repro.core.clipping import CLIP_PATHS
from repro.core.dp_fedavg import finalize_round, server_step
from repro.core.server_optim import ServerOptState, init_state
from repro.data.population_store import PopulationStore, as_population_store
from repro.data.tokenizer import PAD
from repro.fl import pop_sampler
from repro.fl.client import (client_updates, local_deltas,
                             stream_block_sums)
from repro.fl.faults import FaultConfig, fault_fates
# The canonical-reduction primitives live in `repro.fl.reduction` (shared
# with the host round body); re-exported here for backwards compatibility.
from repro.fl.reduction import (CANON_BLOCKS, block_sums as _block_sums,
                                canon_pad, cohort_sum,
                                fold_blocks as _fold_blocks, n_canon_blocks,
                                resolve_chunk)
from repro.launch.mesh import make_cohort_mesh
from repro.models.api import Model
from repro.sharding.specs import (batch_axes, cohort_spec,
                                  sim_mesh_config)
from repro.utils.spans import scope, span

__all__ = ["CANON_BLOCKS", "EngineState", "FaultConfig",
           "POPULATION_BACKENDS", "SAMPLERS", "SimEngine", "canon_pad",
           "cohort_sum", "gather_client_batches", "gather_cohort_batches",
           "n_canon_blocks", "pace_steering_weights", "poisson_select",
           "sample_cohort"]

POPULATION_BACKENDS = ("device", "streamed")
SAMPLERS = ("global", "sharded")


class EngineState(NamedTuple):
    """Device-resident simulation state threaded through the round scan."""

    params: Any
    opt_state: ServerOptState
    key: jax.Array            # PRNG stream (split once per round)
    last_round: jax.Array     # (N,) int32 — last participation, Pace Steering
    participation: jax.Array  # (N,) int32 — per-device participation counts
    round_idx: jax.Array      # () int32


def pace_steering_weights(last_round, synthetic, round_idx,
                          cooldown: int, penalty: float):
    """Default weight hook — mirrors `PopulationSim.selection_weights`:
    devices that participated within ``cooldown`` rounds are deprioritized to
    ``penalty``; secret-sharer synthetic devices are exempt (paper §V-A)."""
    cooling = (round_idx - last_round) < cooldown
    cooling &= ~synthetic
    return jnp.where(cooling, penalty, 1.0)


# Stand-in weight for unavailable devices: log(1e-30) ≈ -69 is far below any
# Gumbel perturbation of a real weight, so they are never chosen while ≥
# cohort available devices exist — but rounds stay fixed-size (and p stays
# finite) when an availability draw comes up short.
_UNAVAILABLE_W = 1e-30


def sample_cohort(key, weights, available, cohort: int):
    """Fixed-size weighted sampling without replacement on device.

    Rounds are fixed-size by construction (Algorithm 1): if a round's
    check-in draw leaves fewer than ``cohort`` devices, the remainder is
    topped up from un-checked-in devices rather than shrinking the round
    (the host loop does the opposite — see ``SimEngine`` for the warning
    when a configuration makes that regime likely)."""
    w = jnp.where(available, weights, _UNAVAILABLE_W).astype(jnp.float32)
    p = w / jnp.sum(w)
    return jax.random.choice(key, w.shape[0], (cohort,), replace=False, p=p)


def poisson_select(key, q: float, available, buffer: int):
    """Per-device Bernoulli(q) round composition [MRTZ17] with static shapes.

    Draws ``sel[i] ~ Bernoulli(q)`` for every *available* device, then packs
    the first ``buffer`` selected device ids (index order — a Poisson round
    is an unordered set) into a fixed-shape cohort buffer. Returns
    ``(ids (buffer,), slot_mask (buffer,) bool, took (N,) bool)`` where
    ``took`` marks exactly the devices occupying a buffer slot. Overflow
    beyond ``buffer`` is truncated; size the buffer ≥ qN + 4·√(qN) so that
    tail is negligible (`SimEngine` warns otherwise).
    """
    sel = (jax.random.uniform(key, available.shape) < q) & available
    took = sel & (jnp.cumsum(sel) <= buffer)
    ids = jnp.nonzero(took, size=buffer, fill_value=0)[0]
    slot_mask = jnp.arange(buffer) < jnp.sum(took)
    return ids, slot_mask, took


def gather_client_batches(examples, counts, ids, keys,
                          n_batches: int, batch_size: int):
    """Build the (C, n_batches, B, S) client batch stack by pure gathers from
    the padded corpus tensor — the device-side analogue of
    ``FederatedDataset.user_tensor`` (uniform-per-example via per-user
    ``counts`` bounds; draws with replacement).

    ``keys`` is a (C,) stack of *per-slot* PRNG keys, split from the
    replicated round stream *before* the cohort axis is sharded — so a
    slot's example draw is independent of the shard count (bit-parity
    across shards), though it does depend on the slot position. Anything
    that re-packs or reorders buffer slots (e.g. per-shard compaction)
    would therefore change the draws; keep slot assignment replicated."""
    need = n_batches * batch_size

    def one(uid, key):
        idx = jax.random.randint(key, (need,), 0, counts[uid])
        return examples[uid][idx].reshape(n_batches, batch_size, -1)

    rows = jax.vmap(one)(ids, keys)                      # (C, nb, B, S+1)
    batch = {"tokens": rows[..., :-1], "labels": rows[..., 1:]}
    batch["mask"] = (batch["labels"] != PAD).astype(jnp.float32)
    return batch


def gather_cohort_batches(cohort_examples, cohort_counts, keys,
                          n_batches: int, batch_size: int):
    """Slot-aligned analogue of :func:`gather_client_batches` for the
    streamed population backend: the cohort's example rows arrive as a
    staged (C, E_max, seq_len+1) buffer (one row-block per *slot*, already
    host-gathered from the `PopulationStore`) instead of being gathered
    from the device-resident corpus tensor by user id.

    Bit-parity contract: ``cohort_examples[slot] == examples[ids[slot]]``
    and ``cohort_counts[slot] == counts[ids[slot]]`` by construction, and
    ``keys`` is the same per-slot key stack the device backend splits — so
    the uniform index draw and the selected token rows are bit-identical to
    the device backend's, whatever the population size behind the store."""
    need = n_batches * batch_size

    def one(ex_u, cnt, key):
        idx = jax.random.randint(key, (need,), 0, cnt)
        return ex_u[idx].reshape(n_batches, batch_size, -1)

    rows = jax.vmap(one)(cohort_examples, cohort_counts, keys)
    batch = {"tokens": rows[..., :-1], "labels": rows[..., 1:]}
    batch["mask"] = (batch["labels"] != PAD).astype(jnp.float32)
    return batch


class _SamplerState(NamedTuple):
    """Device-resident slice of :class:`EngineState` the streamed backend's
    sampler owns — deliberately disjoint from (params, opt_state), which is
    what makes the one-round cohort lookahead legal."""

    key: jax.Array
    last_round: jax.Array
    participation: jax.Array
    round_idx: jax.Array


class SimEngine:
    """K-rounds-per-jit DP-FedAvg simulator over a device-resident population.

    ``data`` is the dict from ``FederatedDataset.to_device_arrays()``. The
    availability / Pace-Steering parameters mirror ``PopulationSim``; pass
    ``weight_fn(last_round, synthetic, round_idx) -> (N,) weights`` to
    replace the Pace-Steering prior (e.g. for sampling-skew ablations).

    ``sampling`` defaults to ``dp.sampling``: ``"fixed"`` rounds of exactly
    qN devices (Algorithm 1), or ``"poisson"`` variable-size rounds (each
    available device i.i.d. Bernoulli(qN/N); Pace-Steering weights don't
    apply — inclusion probability is uniform, matching the host
    ``sample_round(scheme="poisson")`` reference).

    ``num_shards`` / ``num_pods`` (or an explicit cohort ``mesh_config``,
    see `sharding.specs.sim_mesh_config`) shard the cohort axis across
    ``num_pods × num_shards`` devices with ``shard_map`` — a 1-D ``data``
    mesh, or the 2-D ``(pod, data)`` batch slice of the production mesh
    when ``num_pods > 1``. Sampling, noise, and the server step stay
    replicated (params are pod-replicated; only round-sum block partials
    cross the inter-pod axis); only client batching + local training +
    clipping are per-shard, combined by the canonical reduction
    (:func:`cohort_sum` association — bit-identical for every topology
    whose ``num_pods · num_shards`` divides :data:`CANON_BLOCKS`). Needs
    ≥ ``num_pods × num_shards`` visible devices (on CPU force them with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).

    ``cohort_chunk`` streams the round: each canonical block's partial sum
    is accumulated ``cohort_chunk`` clients at a time (gather → local SGD →
    fused clip→accumulate per chunk), so peak update memory is
    O(cohort_chunk·|params|) instead of the materializing O(cohort·|params|)
    stack. The intra-block fold is strictly sequential per slot, so
    trajectories are **bit-identical across every chunk size dividing the
    block size** (padded cohort / :data:`CANON_BLOCKS`), composing with the
    cross-shard parity. ``None`` auto-selects (largest divisor ≤
    `reduction.DEFAULT_MAX_CHUNK`); ``0`` restores the materializing path
    (the validated reference / benchmark baseline — its XLA-reduction
    association is *not* bit-comparable to the streaming family).

    ``population_backend`` selects where the corpus lives: ``"device"``
    (default) keeps the whole padded tensor device-resident (``data`` is a
    ``to_device_arrays()`` dict or a `PopulationStore` to materialize);
    ``"streamed"`` keeps it host-resident behind a `PopulationStore`
    (``data`` may also be a dict — wrapped in-memory — or a store path) and
    stages one cohort per round through two ping-ponged device buffers with
    a one-round prefetch lookahead — O(2·cohort·E_max) device corpus
    residency independent of N, bit-exact against ``"device"`` (see the
    module docstring).

    ``sampler`` selects the cohort-selection implementation: ``"global"``
    (default) is the monolithic O(N)-on-one-device program — availability
    draw, Pace-Steering weights, ``jax.random.choice``'s Gumbel argsort —
    bit-identical to every pre-sampler-knob trajectory; ``"sharded"`` lays
    the population axis out in canonical blocks, draws per-block from
    fold-in-keyed streams, and selects by per-shard Gumbel **top-k** merged
    through a canonical lex sort (`fl.pop_sampler`) — an exact weighted
    sample that shards the O(N) state and work over the same mesh as the
    cohort, with only O(cohort) candidates crossing shards. The two are
    *different sampler families* (different PRNG layouts ⇒ different —
    equally valid — trajectories); within the sharded family trajectories
    are deterministic in the seed and bit-exact across {pods} × {shards} ×
    {chunk} × {device, streamed} × {fixed, poisson} × {faults on/off}.

    ``clip_path`` selects the per-client clip→accumulate implementation:
    ``"fused"`` (default) runs the flat-parameter Pallas ``dp_clip`` kernels
    (interpret mode on CPU, compiled on TPU); ``"tree"`` the pytree
    reference.

    ``eval_fn(params, round_idx) -> pytree`` runs inside the scan on the
    *post-update* params after rounds ``eval_every, 2·eval_every, …``; other
    rounds carry zeros (see history keys ``eval`` / ``eval_mask``).
    """

    def __init__(self, model: Model, data, dp: DPConfig,
                 client: ClientConfig, *,
                 n_local_batches: int = 4, availability: float = 0.1,
                 pace_cooldown: int = 50, pace_penalty: float = 0.01,
                 rounds_per_call: int = 8,
                 weight_fn: Optional[Callable] = None,
                 sampling: Optional[str] = None,
                 poisson_buffer: Optional[int] = None,
                 num_shards: int = 1, num_pods: int = 1,
                 mesh_config: Optional[MeshConfig] = None,
                 cohort_chunk: Optional[int] = None,
                 clip_path: str = "fused",
                 population_backend: str = "device",
                 sampler: str = "global",
                 fault_config: Optional[FaultConfig] = None,
                 eval_fn: Optional[Callable] = None, eval_every: int = 1):
        self.model = model
        self.dp = dp
        self.client = client
        self.n_local_batches = n_local_batches
        self.availability = availability
        self.rounds_per_call = max(int(rounds_per_call), 1)
        self.sampling = sampling or getattr(dp, "sampling", "fixed")
        if self.sampling not in ("fixed", "poisson"):
            raise ValueError(f"sampling must be 'fixed' or 'poisson', "
                             f"got {self.sampling!r}")
        if mesh_config is not None:
            axes = tuple(mesh_config.axes)
            if axes not in (("data",), ("pod", "data")):
                raise ValueError(
                    "SimEngine shards the cohort over its batch axes only "
                    f"— a ('data',) or ('pod', 'data') mesh; got "
                    f"{mesh_config}. Model-parallel axes are the launch "
                    "layer's job — pass sim_mesh_config(num_shards, "
                    "num_pods) or just num_shards/num_pods.")
            sizes = dict(zip(axes, mesh_config.shape))
            from_mesh = sizes["data"]
            from_mesh_pods = sizes.get("pod", 1)
            if num_shards not in (1, from_mesh):
                raise ValueError(
                    f"num_shards={num_shards} disagrees with mesh_config's "
                    f"data axis ({from_mesh} devices); pass one or the "
                    "other")
            if num_pods not in (1, from_mesh_pods):
                raise ValueError(
                    f"num_pods={num_pods} disagrees with mesh_config's pod "
                    f"axis ({from_mesh_pods} pods); pass one or the other")
            num_shards, num_pods = from_mesh, from_mesh_pods
        self.num_shards = int(num_shards)
        self.num_pods = int(num_pods)
        self._mesh_config = sim_mesh_config(self.num_shards, self.num_pods)
        # total devices the cohort axis shards over (pod-major layout)
        self.total_shards = self.num_pods * self.num_shards
        # the cohort axis shards over exactly the batch_axes of the mesh
        # config — same layout rule as the production client dimension
        self._cohort_pspec = cohort_spec(self._mesh_config)
        self.mesh = (make_cohort_mesh(self._mesh_config)
                     if self.total_shards > 1 else None)
        self.eval_fn = eval_fn
        self.eval_every = max(int(eval_every), 1)
        if population_backend not in POPULATION_BACKENDS:
            raise ValueError(f"population_backend must be one of "
                             f"{POPULATION_BACKENDS}, got "
                             f"{population_backend!r}")
        self.population_backend = population_backend
        if population_backend == "device":
            # whole-corpus device residency: the original O(N·E_max·seq_len)
            # layout (a PopulationStore materializes through device_arrays())
            if isinstance(data, PopulationStore):
                data = data.device_arrays()
            self.store = None
            self.examples = jnp.asarray(data["examples"])
            self.counts = jnp.asarray(data["counts"])
            synth_np = np.asarray(data["synthetic"], bool)
            self.emax = int(self.examples.shape[1])
            self.row_len = int(self.examples.shape[2])
        else:
            # host-resident corpus: only the per-user vectors + two staged
            # cohort buffers ever touch the device
            self.store = as_population_store(data)
            self.examples = self.counts = None
            synth_np = np.asarray(self.store.synthetic, bool)
            self.emax = self.store.emax
            self.row_len = self.store.row_len
        self.synthetic = jnp.asarray(synth_np)
        self.n_users = int(synth_np.shape[0])
        self.cohort = min(dp.clients_per_round, self.n_users)
        self.q = self.cohort / self.n_users
        if sampler not in SAMPLERS:
            raise ValueError(f"sampler must be one of {SAMPLERS}, "
                             f"got {sampler!r}")
        self.sampler = sampler
        if sampler == "sharded":
            # population axis laid out in canonical blocks (pop_sampler
            # parity contract): padded length + block grid are fixed across
            # every topology in the parity family, and the per-user vectors
            # (plus this synthetic mask) shard over the batch axes
            self.pop_blocks = pop_sampler.n_pop_blocks(self.num_shards,
                                                       self.num_pods)
            self.n_pad = pop_sampler.pop_pad(self.n_users, self.num_shards,
                                             self.num_pods)
            synth_pad = np.zeros(self.n_pad, bool)
            synth_pad[:self.n_users] = synth_np
            self._synth_pad = jnp.asarray(synth_pad)
            if self.mesh is not None:
                self._synth_pad = jax.device_put(
                    self._synth_pad,
                    NamedSharding(self.mesh, self._cohort_pspec))
        else:
            self.pop_blocks = None
            self.n_pad = self.n_users
            self._synth_pad = None
        # production fault model: over-select so the *expected* survivor
        # count is the target cohort, and calibrate σ (and the released
        # mean) to the report goal — never the realized survivor count.
        # With fault_config=None every derived quantity collapses to its
        # fault-free value, so the traced round program is unchanged.
        self.faults = fault_config
        if self.faults is not None:
            self.report_goal = self.faults.resolve_report_goal(self.cohort)
            self.sel_cohort = min(self.n_users,
                                  self.faults.over_selection(self.cohort))
            self.sel_q = min(1.0, self.q / self.faults.expected_survival
                             ) if self.faults.over_select else self.q
            self._fault_key = jax.random.PRNGKey(self.faults.seed)
            self._round_denom = self.report_goal
        else:
            self.report_goal = None
            self.sel_cohort = self.cohort
            self.sel_q = self.q
            self._fault_key = None
            self._round_denom = self.cohort
        if self.sampling == "poisson":
            exp_sel = (self.cohort if self.faults is None
                       else self.sel_q * self.n_users)
            buf = poisson_buffer or int(np.ceil(
                exp_sel + 4.0 * np.sqrt(exp_sel) + 4))
            # pad, never truncate: a buffer that doesn't divide the shard
            # count grows to the next canonical multiple (masked empty
            # slots) so no selected device is silently dropped
            self.buffer = canon_pad(min(self.n_users, buf), self.num_shards,
                                    self.num_pods)
            if self.buffer < self.cohort + 2 * np.sqrt(self.cohort) \
                    and self.buffer < self.n_users:
                import warnings
                warnings.warn(
                    f"SimEngine: poisson_buffer={self.buffer} is within 2σ "
                    f"of the expected round size qN={self.cohort}; rounds "
                    "will regularly be truncated (the clipped sum silently "
                    "drops the overflow). Raise poisson_buffer.",
                    stacklevel=2)
        else:
            self.buffer = self.sel_cohort
        # the physical per-round buffer: (over-)selected / poisson slots
        # padded to the canonical block grid (slot_mask zeroes the padding
        # exactly; sel_cohort == cohort whenever faults are off)
        self.padded = (self.buffer if self.sampling == "poisson"
                       else canon_pad(self.sel_cohort, self.num_shards,
                                      self.num_pods))
        self.n_blocks = n_canon_blocks(self.num_shards, self.num_pods)
        if self.padded % self.total_shards or self.padded % self.n_blocks:
            raise AssertionError(
                f"SimEngine internal error: padded cohort buffer "
                f"{self.padded} must be divisible by num_pods×num_shards="
                f"{self.total_shards} and n_blocks={self.n_blocks} — "
                "padding must never truncate devices (ragged cohorts pad "
                "up)")
        if clip_path not in CLIP_PATHS:
            raise ValueError(f"clip_path must be one of {CLIP_PATHS}, "
                             f"got {clip_path!r}")
        self.clip_path = clip_path
        # streaming accumulation: chunk size per canonical block (0 = the
        # legacy materializing path, kept for benchmarking/validation)
        self.cohort_chunk = resolve_chunk(cohort_chunk,
                                          self.padded // self.n_blocks)
        if self.faults is not None:
            if self.cohort_chunk == 0:
                raise ValueError(
                    "fault_config needs the streaming accumulation path "
                    "(cohort_chunk > 0): corrupt-report rejection lives in "
                    "the per-slot fold's guard_nonfinite — the materializing "
                    "cohort_chunk=0 path is the fault-free reference only")
            max_survivors = (self.sel_cohort if self.sampling == "fixed"
                             else self.padded)
            if self.report_goal > max_survivors:
                import warnings
                warnings.warn(
                    f"SimEngine: report_goal={self.report_goal} exceeds the "
                    f"per-round selection ({max_survivors} slots) — every "
                    "round will abort and the run can never make progress. "
                    "Lower report_goal or enable over_select.", stacklevel=2)
        n_synth = int(synth_np.sum())
        expected_avail = availability * (self.n_users - n_synth) + n_synth
        if self.sampling == "fixed" and expected_avail < self.sel_cohort:
            import warnings
            warnings.warn(
                f"SimEngine: expected check-ins ({expected_avail:.0f} = "
                f"{availability}·{self.n_users - n_synth} real + {n_synth} "
                f"synthetic) < cohort ({self.sel_cohort}); fixed-size rounds "
                "will regularly be topped up from un-checked-in devices and "
                "σ = zS/qN assumes the full cohort. Raise availability / "
                "population or lower clients_per_round.", stacklevel=2)
        if self.sampling == "poisson" \
                and self.q * expected_avail < 0.9 * self.cohort:
            import warnings
            warnings.warn(
                f"SimEngine: Poisson rounds select Bernoulli(q={self.q:.3g})"
                f" among *available* devices — expected realized round size "
                f"({self.q * expected_avail:.0f}) is well below qN "
                f"({self.cohort}) while σ = zS/qN assumes qN. Per-round SNR "
                "will be worse than the DPConfig calibration implies; raise "
                "availability (MRTZ17 assumes the whole population is "
                "available) or lower clients_per_round.", stacklevel=2)
        self.weight_fn = weight_fn or (
            lambda last, synth, r: pace_steering_weights(
                last, synth, r, pace_cooldown, pace_penalty))
        # batch-source dispatch: how a (cohort-sharded) tuple of per-slot
        # arrays becomes the (C, nb, B, S) client batch stack — by-user-id
        # gathers from the device corpus, or by-slot gathers from a staged
        # cohort buffer (see _batch_args for the matching tuple layout)
        if self.population_backend == "device":
            self._gather_batches = lambda a: gather_client_batches(
                self.examples, self.counts, a[0], a[1],
                self.n_local_batches, self.client.batch_size)
        else:
            self._gather_batches = lambda a: gather_cohort_batches(
                a[0], a[1], a[2], self.n_local_batches,
                self.client.batch_size)
        self._compiled: Dict[int, Callable] = {}
        # streamed backend: (sample_jit, compute_jit) per donation policy,
        # plus the two ping-ponged staged-cohort device buffer slots
        self._streamed_jits: Dict[bool, Tuple[Callable, Callable]] = {}
        self._cohort_sharding = (NamedSharding(self.mesh, self._cohort_pspec)
                                 if self.mesh is not None else None)
        self._inflight = [None, None]
        if self.population_backend == "device":
            # reference path keeps its inputs alive (no donation) so tests
            # can replay the same initial state through both entry points
            self._one_round = jax.jit(self._round_body)

    # ------------------------------------------------------------------ state

    def init_state(self, params, seed: int = 0,
                   opt_state: Optional[ServerOptState] = None) -> EngineState:
        # the sharded sampler owns (n_pad,) population vectors — padded to
        # the canonical population block grid and mesh-sharded; the global
        # sampler keeps the exact (n_users,) replicated layout
        state = EngineState(
            params=params,
            opt_state=opt_state if opt_state is not None else init_state(params),
            key=jax.random.PRNGKey(seed),
            last_round=jnp.full((self.n_pad,), -(10 ** 9), jnp.int32),
            participation=jnp.zeros((self.n_pad,), jnp.int32),
            round_idx=jnp.zeros((), jnp.int32))
        return self.place_state(state)

    def place_state(self, state: EngineState) -> EngineState:
        """Commit an :class:`EngineState` to the engine's device layout (the
        init / run-state-restore placement): everything replicated across
        the cohort mesh — except the population vectors under
        ``sampler="sharded"``, which shard over the batch axes so the
        donated round bodies keep one stable layout. No-op off-mesh."""
        if self.mesh is None:
            return state
        repl = NamedSharding(self.mesh, P())
        if self.sampler == "global":
            # commit replicated across the cohort mesh so the donated scan
            # carry keeps one stable layout (no resharding between chunks)
            return jax.device_put(state, NamedSharding(self.mesh, P()))
        pop = NamedSharding(self.mesh, self._cohort_pspec)
        return EngineState(
            params=jax.device_put(state.params, repl),
            opt_state=jax.device_put(state.opt_state, repl),
            key=jax.device_put(state.key, repl),
            last_round=jax.device_put(state.last_round, pop),
            participation=jax.device_put(state.participation, pop),
            round_idx=jax.device_put(state.round_idx, repl))

    # ------------------------------------------------------------- round body

    def _local_block_sums(self, params, batch_args, slot_mask,
                          n_blocks: int, corrupt=None):
        """Per-shard slice of the round: gather → local SGD → clip → masked
        canonical block partial sums. Returns (update-block pytree with a
        leading (n_blocks,) axis, (n_blocks, 4) stat blocks packing
        [Σ norms, Σ clipped-flags, Σ losses, Σ mask]). Streams
        ``cohort_chunk`` clients at a time unless ``cohort_chunk == 0``
        (the legacy materializing path).

        ``batch_args`` is the backend's per-slot batch-source tuple (every
        leaf carries a leading cohort-slot axis): ``(ids, keys)`` for the
        device-resident corpus, ``(cohort_examples, cohort_counts, keys)``
        for a staged cohort buffer — `_gather_batches` turns either into
        the (C, nb, B, S) client batch stack.

        ``corrupt`` (fault model only, (slots,) bool) marks slots whose
        report arrives as non-finite garbage — injected after local SGD,
        rejected by the fold's guard."""
        if self.cohort_chunk == 0:
            return self._materialized_block_sums(params, batch_args,
                                                 slot_mask, n_blocks)
        return self._streamed_block_sums(params, batch_args, slot_mask,
                                         n_blocks, corrupt)

    def _streamed_block_sums(self, params, batch_args, slot_mask,
                             n_blocks: int, corrupt=None):
        """Streaming accumulation: a scan over contiguous ``cohort_chunk``
        slices of each canonical block runs gather → local SGD per chunk and
        folds the chunk's clipped updates into the block's running partial
        (`fl.client.stream_block_sums`) — peak update memory is
        O(cohort_chunk·|params|), fully-masked padding chunks skip their
        compute, and the per-slot fold keeps the canonical intra-block
        association so every dividing chunk size is bit-identical.

        With ``corrupt`` the chunk compute poisons the marked slots' deltas
        and losses with NaN (multiplicative, so clean slots are bitwise
        untouched) and the fold runs with ``guard_nonfinite`` — the
        end-to-end corrupt-report injection + server-side rejection of the
        production fault model."""
        chunk = self.cohort_chunk
        cpb = slot_mask.shape[0] // (n_blocks * chunk)   # chunks per block
        shape3 = (n_blocks, cpb, chunk)
        args_r = jax.tree_util.tree_map(
            lambda l: l.reshape(shape3 + l.shape[1:]), batch_args)
        mask_r = slot_mask.astype(jnp.float32).reshape(shape3)

        if corrupt is None:
            def compute_chunk(inputs):
                with scope("client_sgd"):
                    batches = self._gather_batches(inputs)
                    return local_deltas(self.model, params, batches,
                                        self.client)

            inputs, guard = args_r, False
        else:
            corrupt_r = corrupt.astype(jnp.float32).reshape(shape3)

            def compute_chunk(inputs):
                args, bad = inputs
                with scope("client_sgd"):
                    batches = self._gather_batches(args)
                    deltas, losses = local_deltas(self.model, params,
                                                  batches, self.client)
                # multiply by 1 (clean) or NaN (corrupt): x·1 is a bitwise
                # identity, x·NaN wrecks every element — the guard must
                # reject the whole report, not salvage parts of it
                poison = jnp.where(bad > 0, jnp.float32(jnp.nan),
                                   jnp.float32(1.0))
                deltas = jax.tree_util.tree_map(
                    lambda l: l * poison.reshape((-1,) + (1,) * (l.ndim - 1)),
                    deltas)
                return deltas, losses * poison

            inputs, guard = (args_r, corrupt_r), True

        return stream_block_sums(compute_chunk, inputs, mask_r,
                                 params, self.dp.clip_norm,
                                 clip_path=self.clip_path,
                                 guard_nonfinite=guard)

    def _materialized_block_sums(self, params, batch_args, slot_mask,
                                 n_blocks: int):
        """Legacy materializing path (``cohort_chunk=0``): vmap the whole
        padded slice, stack every clipped update, block-reduce once —
        O(cohort·|params|) peak memory, XLA-reduction association. Kept as
        the validated reference and the benchmark baseline."""
        batches = self._gather_batches(batch_args)
        clipped, norms, flags, losses = client_updates(
            self.model, params, batches, self.client, self.dp)
        m = slot_mask.astype(jnp.float32)
        tree = jax.tree_util.tree_map(
            lambda l: _block_sums(
                l.astype(jnp.float32) * m.reshape((-1,) + (1,) * (l.ndim - 1)),
                n_blocks),
            clipped)
        scal = _block_sums(jnp.stack([norms * m, flags * m, losses * m, m],
                                     axis=-1), n_blocks)
        return tree, scal

    def _cohort_sums(self, params, ids, keys, slot_mask, corrupt=None):
        """Device-backend entry: batch args are (ids, keys) gathers from the
        device-resident corpus tensor. See :meth:`_cohort_sums_from`."""
        return self._cohort_sums_from(params, (ids, keys), slot_mask,
                                      corrupt)

    def _cohort_sums_from(self, params, batch_args, slot_mask, corrupt=None):
        """Global masked clipped sum + stat sums over the padded cohort
        buffer — per-shard compute under ``shard_map``, combined by the
        canonical block tree so every (pod, shard) topology whose total
        divides the block count agrees bitwise. On the 2-D ``(pod, data)``
        mesh the reduction is hierarchical: each pod gathers and folds its
        own contiguous block group over the intra-pod ``data`` axis, and
        only those pod partials cross the inter-pod ``pod`` axis (where the
        same pairwise tree combines them — `reduction.fold_pods`
        association). ``batch_args`` leaves shard along their leading
        cohort-slot axis (same spec as ``slot_mask``); ``corrupt`` (fault
        model only) shards the same way — fates are slot-level, so the
        injection/rejection lands on the same slots whatever the
        topology."""
        if self.total_shards == 1:
            tree, scal = self._local_block_sums(params, batch_args,
                                                slot_mask, self.n_blocks,
                                                corrupt)
            with scope("clip_fold"):
                return (jax.tree_util.tree_map(_fold_blocks, tree),
                        _fold_blocks(scal))

        cspec = self._cohort_pspec
        axes = batch_axes(self._mesh_config)  # ("data",) or ("pod", "data")
        data_axis = axes[-1]
        nblk_local = self.n_blocks // self.total_shards
        nblk_pod = self.n_blocks // self.num_pods

        def body(params, batch_args, slot_mask, corrupt=None):
            tree, scal = self._local_block_sums(params, batch_args,
                                                slot_mask, nblk_local,
                                                corrupt)
            with scope("clip_fold"):
                # all_gather carries the raw block partials (no
                # arithmetic), so the pairwise tree below is evaluated
                # identically — and with the identical association — on
                # every shard. The cohort layout is pod-major, so gathering
                # over the data axis yields this pod's contiguous block
                # group in canonical order.
                gather_d = lambda l: jax.lax.all_gather(l, data_axis).reshape(
                    (nblk_pod,) + l.shape[1:])
                if self.num_pods == 1:
                    tree = jax.tree_util.tree_map(gather_d, tree)
                    return (jax.tree_util.tree_map(_fold_blocks, tree),
                            _fold_blocks(gather_d(scal)))
                # pod-local fold first: only the folded pod partials — one
                # |params|-sized value per pod, not per block — cross the
                # expensive inter-pod links
                pod_tree = jax.tree_util.tree_map(
                    lambda l: _fold_blocks(gather_d(l)), tree)
                pod_scal = _fold_blocks(gather_d(scal))
                gather_p = lambda l: jax.lax.all_gather(l, "pod")
                tree = jax.tree_util.tree_map(
                    lambda l: _fold_blocks(gather_p(l)), pod_tree)
                return tree, _fold_blocks(gather_p(pod_scal))

        # cspec is a pytree *prefix*: it shards every batch_args leaf along
        # its leading cohort-slot axis, whatever the backend's tuple layout.
        # The fault-free signature is kept verbatim so fault-off programs
        # trace exactly as before. check_vma is off: the body closes over
        # replicated population constants.
        if corrupt is None:
            sharded = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(), cspec, cspec), out_specs=P(),
                check_vma=False)
            return sharded(params, batch_args, slot_mask)
        sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), cspec, cspec, cspec), out_specs=P(),
            check_vma=False)
        return sharded(params, batch_args, slot_mask, corrupt)

    def _pop_shard_body(self, rank, k_avail, k_sample, round_idx,
                        last_round, participation, synthetic, axes=None):
        """One shard's slice of the sharded sampler round: block-keyed
        availability / score / Bernoulli draws over the shard's contiguous
        population rows, local candidate selection, the canonical
        (replicated) merge, fault fates, and the O(cohort) masked scatter
        updates of the local population-vector rows. Runs identically as
        the whole program when ``total_shards == 1`` (``rank=0``,
        ``axes=None`` skips the gathers) — the merge consumes the same
        candidate lists either way, which is the topology bit-exactness
        argument (see `fl.pop_sampler`)."""
        n_loc = last_round.shape[0]              # n_pad / total_shards
        nb_loc = self.pop_blocks // self.total_shards
        blk = n_loc // nb_loc
        offset = rank * n_loc
        block_ids = rank * nb_loc + jnp.arange(nb_loc)
        valid = (offset + jnp.arange(n_loc)) < self.n_users
        avail = ((pop_sampler.block_uniforms(k_avail, block_ids, blk)
                  .reshape(-1) < self.availability) | synthetic) & valid
        if self.sampling == "poisson":
            u = pop_sampler.block_uniforms(k_sample, block_ids, blk
                                           ).reshape(-1)
            sel = (u < self.sel_q) & avail
            gids, cnt = pop_sampler.pack_selected(sel, self.padded, offset)
            if axes is not None:
                gids = pop_sampler.gather_shards(gids, axes)
                cnt = pop_sampler.gather_shards(cnt[None], axes)
            ids, slot_mask = pop_sampler.merge_poisson(gids, cnt,
                                                       self.padded)
        else:
            w = self.weight_fn(last_round, synthetic, round_idx)
            g = pop_sampler.block_gumbels(k_sample, block_ids, blk
                                          ).reshape(-1)
            score = jnp.log(jnp.where(avail, w.astype(jnp.float32),
                                      _UNAVAILABLE_W)) + g
            skey = jnp.where(valid, pop_sampler.sortable_f32(score),
                             pop_sampler.INT32_MIN)
            k_loc = min(self.sel_cohort, n_loc)
            vals, lidx = pop_sampler.blocked_topk(skey, k_loc)
            gids = (offset + lidx).astype(jnp.int32)
            if axes is not None:
                vals = pop_sampler.gather_shards(vals, axes)
                gids = pop_sampler.gather_shards(gids, axes)
            cohort_ids = pop_sampler.merge_topk(vals, gids, self.sel_cohort)
            ids = jnp.pad(cohort_ids, (0, self.padded - self.sel_cohort))
            slot_mask = jnp.arange(self.padded) < self.sel_cohort
        if self.faults is None:
            report_mask, corrupt = slot_mask, None
        else:
            # replicated math from replicated inputs: every shard computes
            # the identical fates (the stream is slot-level, exactly as in
            # global mode)
            fates = fault_fates(self._fault_key, round_idx, self.padded,
                                self.faults)
            report_mask = slot_mask & fates.reported
            corrupt = report_mask & fates.corrupt
        # O(cohort) local scatters — same semantics as the global path:
        # last_round reacts to selection, participation to arrived reports
        last_round = pop_sampler.scatter_max(last_round, ids, slot_mask,
                                             round_idx, offset)
        part_mask = slot_mask if self.faults is None else report_mask
        participation = pop_sampler.scatter_add(participation, ids,
                                                part_mask, offset)
        out = (last_round, participation, ids, slot_mask, report_mask)
        return out if corrupt is None else out + (corrupt,)

    def _sharded_select(self, k_avail, k_sample, round_idx, last_round,
                        participation):
        """Dispatch :meth:`_pop_shard_body` — directly on one device, or
        under ``shard_map`` over the cohort mesh with the population
        vectors sharded along the batch axes and everything else
        replicated."""
        if self.total_shards == 1:
            out = self._pop_shard_body(0, k_avail, k_sample, round_idx,
                                       last_round, participation,
                                       self._synth_pad)
        else:
            axes = batch_axes(self._mesh_config)
            pspec = self._cohort_pspec

            def body(k_a, k_s, r, lr, part, synth):
                rank = pop_sampler.shard_rank(axes, self.num_shards)
                return self._pop_shard_body(rank, k_a, k_s, r, lr, part,
                                            synth, axes=axes)

            n_out = 5 if self.faults is None else 6
            out = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=(P(), P(), P(), pspec, pspec, pspec),
                out_specs=(pspec, pspec) + (P(),) * (n_out - 2),
                check_vma=False)(
                    k_avail, k_sample, round_idx, last_round, participation,
                    self._synth_pad)
        if self.faults is None:
            return out + (None,)
        return out

    def _sample_phase(self, key, last_round, participation, round_idx):
        """The round's sampling prefix, shared verbatim by the device scan
        body (:meth:`_round_body`) and the streamed sampler body
        (:meth:`_sample_body`) — one definition is what guarantees both
        backends consume the identical PRNG stream. Draws availability,
        selects the (over-selected, with faults) cohort, resolves per-slot
        fault fates, and updates the population vectors.

        Returns ``(key', last_round', participation', (ids, slot_mask,
        report_mask, corrupt, keys, k_noise))``. With ``fault_config=None``
        the report mask *is* the slot mask and ``corrupt`` is None — the
        traced program is the pre-fault-model round prefix. Fault semantics:
        Pace Steering (``last_round``) reacts to *selection* — the server
        contacted the device whatever happened next — while
        ``participation`` counts only slots whose report actually arrived
        (dropped/late excluded; corrupt reports did arrive, so they
        count).

        ``sampler="sharded"`` swaps the monolithic selection (global
        availability draw + ``random.choice``'s argsort over N) for the
        block-local Gumbel top-k of `fl.pop_sampler` — a *different*
        sampler family (its PRNG layout is per-block), deterministic in the
        seed and bit-exact across topologies/backends/chunk sizes, sharing
        this same top-level key split so ``keys``/``k_noise`` (and hence
        the whole compute phase given a cohort) are family-independent."""
        key, k_avail, k_sample, k_idx, k_noise = jax.random.split(key, 5)
        if self.sampler == "sharded":
            last_round, participation, ids, slot_mask, report_mask, \
                corrupt = self._sharded_select(k_avail, k_sample, round_idx,
                                               last_round, participation)
            keys = jax.random.split(k_idx, self.padded)
            return (key, last_round, participation,
                    (ids, slot_mask, report_mask, corrupt, keys, k_noise))
        avail = (jax.random.uniform(k_avail, (self.n_users,))
                 < self.availability) | self.synthetic
        if self.sampling == "poisson":
            ids, slot_mask, took = poisson_select(k_sample, self.sel_q,
                                                  avail, self.padded)
        else:
            w = self.weight_fn(last_round, self.synthetic, round_idx)
            cohort_ids = sample_cohort(k_sample, w, avail, self.sel_cohort)
            ids = jnp.pad(cohort_ids, (0, self.padded - self.sel_cohort))
            slot_mask = jnp.arange(self.padded) < self.sel_cohort
        if self.faults is None:
            report_mask, corrupt = slot_mask, None
        else:
            # slot-level fates from the dedicated fault stream: replicated,
            # independent of the training chain, stateless in round_idx
            fates = fault_fates(self._fault_key, round_idx, self.padded,
                                self.faults)
            report_mask = slot_mask & fates.reported
            corrupt = report_mask & fates.corrupt
        if self.sampling == "poisson":
            last_round = jnp.where(took, round_idx, last_round)
            if self.faults is None:
                participation = participation + took.astype(jnp.int32)
            else:
                participation = participation.at[ids].add(
                    report_mask.astype(jnp.int32))
        else:
            # padded slots alias device 0 — scatter through the mask so they
            # never touch the population vectors
            last_round = last_round.at[ids].max(
                jnp.where(slot_mask, round_idx, jnp.int32(-(10 ** 9))))
            participation = participation.at[ids].add(
                (slot_mask if self.faults is None
                 else report_mask).astype(jnp.int32))
        keys = jax.random.split(k_idx, self.padded)
        return (key, last_round, participation,
                (ids, slot_mask, report_mask, corrupt, keys, k_noise))

    def _compute_phase(self, params, opt_state, round_idx, batch_args,
                       slot_mask, report_mask, corrupt, k_noise):
        """The round's compute suffix, shared by both backends: masked
        clipped sum over the reporting slots → finalize (noise) → server
        step — with the fault model, committed only if accepted survivors
        reach the report goal, otherwise aborted via ``lax.cond`` (params
        and opt state pass through bit-unchanged; the noise key was already
        consumed by the replicated draw, so the PRNG stream — and therefore
        every later round's sampling — is independent of the verdict)."""
        n_selected = jnp.sum(slot_mask).astype(jnp.int32)
        total, scal = self._cohort_sums_from(params, batch_args,
                                             report_mask, corrupt)
        denom = jnp.maximum(scal[3], 1.0)
        mean_norm, frac_clipped, loss = (scal[0] / denom, scal[1] / denom,
                                         scal[2] / denom)
        # Δ̄ and σ are calibrated against a *fixed* denominator — qN (the
        # exact fixed-mode round size / the expected Poisson one [MRTZ17]),
        # or the report goal under the fault model, never the realized
        # survivor count. The noise key is the replicated stream: one draw,
        # every shard agrees.
        with scope("server_step"):
            delta, stats = finalize_round(total, self._round_denom, k_noise,
                                          self.dp,
                                          stats=(mean_norm, frac_clipped))
        if self.faults is None:
            with scope("server_step"):
                params, opt_state = server_step(params, opt_state, delta,
                                                self.dp)
            rec = {"loss": loss, "mean_update_norm": mean_norm,
                   "frac_clipped": frac_clipped,
                   "noise_std": stats.noise_std, "n_clients": n_selected}
        else:
            # scal[3] = Σ report_mask minus guard-rejected slots: exactly
            # the usable reports the production server counts against the
            # report goal before deciding to commit
            n_accepted = scal[3].astype(jnp.int32)
            n_reported = jnp.sum(report_mask).astype(jnp.int32)
            committed = scal[3] >= jnp.float32(self.report_goal)
            with scope("server_step"):
                params, opt_state = jax.lax.cond(
                    committed,
                    lambda po: server_step(po[0], po[1], delta, self.dp),
                    lambda po: po,
                    (params, opt_state))
            rec = {"loss": loss, "mean_update_norm": mean_norm,
                   "frac_clipped": frac_clipped,
                   "noise_std": stats.noise_std, "n_clients": n_accepted,
                   "n_selected": n_selected, "n_reported": n_reported,
                   "committed": committed}
        if self.eval_fn is not None:
            do = ((round_idx + 1) % self.eval_every) == 0
            out_shapes = jax.eval_shape(self.eval_fn, params, round_idx)
            zeros = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), out_shapes)
            rec["eval"] = jax.lax.cond(
                do, lambda p: self.eval_fn(p, round_idx),
                lambda p: zeros, params)
            rec["eval_mask"] = do
        return params, opt_state, rec

    def _round_body(self, state: EngineState, _=None
                    ) -> Tuple[EngineState, Dict[str, jax.Array]]:
        with scope("sample"):
            key, last_round, participation, \
                (ids, slot_mask, report_mask, corrupt, keys, k_noise) = \
                self._sample_phase(state.key, state.last_round,
                                   state.participation, state.round_idx)
        params, opt_state, rec = self._compute_phase(
            state.params, state.opt_state, state.round_idx, (ids, keys),
            slot_mask, report_mask, corrupt, k_noise)
        new_state = EngineState(params, opt_state, key, last_round,
                                participation, state.round_idx + 1)
        return new_state, rec

    def _run_k(self, k: int) -> Callable:
        """jit of a k-round scan with state-buffer donation (params/opt/
        population vectors are updated in place across chunk calls)."""
        if k not in self._compiled:
            def run(state):
                return jax.lax.scan(self._round_body, state, None, length=k)
            self._compiled[k] = jax.jit(run, donate_argnums=0)
        return self._compiled[k]

    # ------------------------------------------- streamed population backend

    def _sample_body(self, sstate: _SamplerState):
        """Round-k cohort selection + population-vector updates — delegating
        to the same :meth:`_sample_phase` the device scan body uses (so the
        streamed backend samples bit-identical cohorts and fault fates),
        owning only the O(N)-vector state. Returns the advanced sampler
        state plus everything the host needs to stage the cohort: ``(ids,
        slot/report/corrupt masks, per-slot keys, k_noise, this round's
        index)``."""
        with scope("sample"):
            key, last_round, participation, \
                (ids, slot_mask, report_mask, corrupt, keys, k_noise) = \
                self._sample_phase(sstate.key, sstate.last_round,
                                   sstate.participation, sstate.round_idx)
        new = _SamplerState(key, last_round, participation,
                            sstate.round_idx + 1)
        return new, (ids, slot_mask, report_mask, corrupt, keys, k_noise,
                     sstate.round_idx)

    def _compute_body(self, params, opt_state, round_idx, cohort_examples,
                      cohort_counts, slot_mask, report_mask, corrupt, keys,
                      k_noise):
        """Round-k compute over a staged cohort buffer — the same
        :meth:`_compute_phase` suffix as the scan path, reading example rows
        by *slot* from the (padded, E_max, seq_len+1) buffer instead of by
        user id from the device corpus. Donated (params, opt_state) keep the
        compile-once, update-in-place behavior of the scan path."""
        return self._compute_phase(
            params, opt_state, round_idx,
            (cohort_examples, cohort_counts, keys), slot_mask, report_mask,
            corrupt, k_noise)

    def _streamed_fns(self, donate: bool) -> Tuple[Callable, Callable]:
        """(sample_jit, compute_jit), compiled once per donation policy:
        ``run`` donates (in-place state updates, two live cohort buffers);
        ``run_python`` keeps inputs alive so tests can replay states."""
        if donate not in self._streamed_jits:
            self._streamed_jits[donate] = (
                jax.jit(self._sample_body,
                        donate_argnums=(0,) if donate else ()),
                jax.jit(self._compute_body,
                        donate_argnums=(0, 1) if donate else ()))
        return self._streamed_jits[donate]

    def _stage_cohort(self, ids: np.ndarray, slot: int, round_idx: int):
        """Host-gather one cohort's example rows from the PopulationStore
        and start their host→device transfer into buffer ``slot`` (two slots
        ping-pong so at most two staged cohorts are ever device-live — the
        one computing and the one prefetching). ``round_idx`` labels the
        spans."""
        with span("fl.gather", round=round_idx):
            ex = self.store.gather(ids)
            cnt = self.store.gather_counts(ids)
        with span("fl.put", round=round_idx):
            if self._cohort_sharding is not None:
                staged = (jax.device_put(ex, self._cohort_sharding),
                          jax.device_put(cnt, self._cohort_sharding))
            else:
                staged = (jax.device_put(ex), jax.device_put(cnt))
        self._inflight[slot] = staged   # overwriting frees round k−2's pair
        return staged

    def _run_streamed(self, state: EngineState, n_rounds: int, *,
                      donate: bool, prefetch: bool
                      ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Host-driven round loop over the two jitted bodies. With
        ``prefetch`` the loop runs one round ahead on the sampler chain:
        round k+1's cohort ids are sampled, host-gathered, and device_put
        while round k's (asynchronously dispatched) chunked compute scan is
        still in flight — the double-buffered pipeline. Without it, rounds
        stage-then-compute sequentially (the reference dispatch order);
        both orders consume identical PRNG streams and are bit-identical."""
        sample_jit, compute_jit = self._streamed_fns(donate)
        # the host's copy of the first round's index, for the spans (the
        # sampler state is donated below)
        r0 = int(state.round_idx)
        sstate = _SamplerState(state.key, state.last_round,
                               state.participation, state.round_idx)
        params, opt_state = state.params, state.opt_state

        def sample_and_stage(sstate, r):
            with span("fl.sample", round=r0 + r):
                sstate, (ids, slot_mask, report_mask, corrupt, keys,
                         k_noise, ridx) = sample_jit(sstate)
            # the only per-round host sync: the (padded,) id vector
            with span("fl.ids_wait", round=r0 + r):
                ids = np.asarray(ids)
            ex, cnt = self._stage_cohort(ids, r % 2, r0 + r)
            return sstate, (ridx, ex, cnt, slot_mask, report_mask, corrupt,
                            keys, k_noise)

        recs = []
        if prefetch:
            sstate, staged = sample_and_stage(sstate, 0)
            for r in range(n_rounds):
                with span("fl.compute", round=r0 + r):
                    params, opt_state, rec = compute_jit(params, opt_state,
                                                         *staged)
                if r + 1 < n_rounds:
                    # overlaps the compute dispatched just above
                    sstate, staged = sample_and_stage(sstate, r + 1)
                recs.append(rec)
        else:
            for r in range(n_rounds):
                sstate, staged = sample_and_stage(sstate, r)
                with span("fl.compute", round=r0 + r):
                    params, opt_state, rec = compute_jit(params, opt_state,
                                                         *staged)
                recs.append(rec)
        with span("fl.history", round=r0 + n_rounds - 1):
            recs = jax.device_get(recs)
        hist = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *recs)
        self._inflight = [None, None]
        new_state = EngineState(params, opt_state, sstate.key,
                                sstate.last_round, sstate.participation,
                                sstate.round_idx)
        return new_state, hist

    def run_sampler(self, state: EngineState, n_rounds: int) -> EngineState:
        """Sampling-only loop (benchmark attribution): advance the sampler
        chain — cohort selection + population-vector updates — ``n_rounds``
        times through the same jitted :meth:`_sample_body` both backends
        use, skipping staging and compute. Consumes the round PRNG stream
        exactly as a full round would, so wall time here *is* the round's
        ``sample_s`` share. Inputs are kept alive (no donation)."""
        sample_jit, _ = self._streamed_fns(False)
        sstate = _SamplerState(state.key, state.last_round,
                               state.participation, state.round_idx)
        for _ in range(n_rounds):
            sstate, out = sample_jit(sstate)
        jax.block_until_ready(sstate)
        return EngineState(state.params, state.opt_state, sstate.key,
                           sstate.last_round, sstate.participation,
                           sstate.round_idx)

    # ------------------------------------------------------------------ entry

    def run(self, state: EngineState, n_rounds: int
            ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Compiled path: scan ``rounds_per_call`` rounds per jit call.
        Returns (state, history pytree of arrays with a leading (n_rounds,)
        axis — scalars per round for the training metrics, the stacked
        ``eval_fn`` output pytree under ``"eval"`` when a hook is set).

        On the streamed population backend this is the double-buffered
        host-driven loop instead (one round per compute call, cohort k+1
        staging under cohort k's compute; ``rounds_per_call`` is a no-op
        there); donation semantics are identical — the input state is
        consumed either way."""
        if n_rounds <= 0:
            return state, {}
        if self.population_backend == "streamed":
            return self._run_streamed(state, n_rounds, donate=True,
                                      prefetch=True)
        hists = []
        left = n_rounds
        while left > 0:
            k = min(self.rounds_per_call, left)
            state, h = self._run_k(k)(state)
            hists.append(jax.device_get(h))
            left -= k
        hist = jax.tree_util.tree_map(
            lambda *xs: np.concatenate(xs), *hists)
        return state, hist

    def run_python(self, state: EngineState, n_rounds: int
                   ) -> Tuple[EngineState, Dict[str, np.ndarray]]:
        """Reference path: the same round body, one jit entry per round.
        Consumes the identical PRNG stream as :meth:`run`, so cohorts,
        batches, and noise match round for round. On the streamed backend:
        the non-donating, non-prefetching (stage-then-compute) dispatch of
        the same two jitted bodies — bit-identical to :meth:`run`."""
        if n_rounds <= 0:
            return state, {}
        if self.population_backend == "streamed":
            return self._run_streamed(state, n_rounds, donate=False,
                                      prefetch=False)
        recs = []
        for _ in range(n_rounds):
            state, rec = self._one_round(state)
            recs.append(jax.device_get(rec))
        hist = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *recs)
        return state, hist

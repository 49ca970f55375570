"""Compile rehearsal: the main path's Pallas kernels, compiled for one
described TPU v5e chip at the paper model's widths (vocab 10,000, embedding
96, CIFG hidden 256). Nothing runs; the TPU compiler refuses here what it
would refuse on the chip (tile misalignment, VMEM over-use), and
``tpu_custom_call`` in the compiled text proves Pallas was compiled, not
interpreted.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file. Code that picks interpret mode from ``jax.default_backend()``
still sees the CPU here, so whole jitted steps are compiled with the two
``default_interpret`` functions patched to the chip's choice. The one test
that runs (`test_embed_grad_on_chip`) is skipped off the chip.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.clipping import clip_accumulate_tree
from repro.kernels.cifg_cell import cifg_cell as CK
from repro.kernels.cifg_cell import ops as CO
from repro.kernels.dp_clip import dp_clip as DK
from repro.kernels.embed_grad import embed_grad as EK
from repro.kernels.embed_grad.ops import embed_lookup
from repro.kernels.embed_grad.ref import embed_lookup_ref
from repro.kernels.lm_head_xent import lm_head_xent as HK
from repro.models import build

H = 256          # CIFG hidden
SLOTS = 16       # serving decode slots / padded client batch rows
CHUNK = 32       # vmapped cohort chunk
SEQ = 16         # client sequence length
D = 96           # tied embedding width
VOCAB, VPAD = 10_000, 10_240
CLIENT_ROWS = 50 * SEQ   # one client's batch of 50 windows
HEAD_CHUNK = 25          # the engine's client chunk at the cell's cohort
EMBED_TILES = (7680, DK.LANES)   # the 10,240 x 96 embedding leaf, tiled


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Pick compiled Pallas as the chip would, for whole jitted steps."""
    monkeypatch.setattr(DK, "default_interpret", lambda: False)
    monkeypatch.setattr(CK, "default_interpret", lambda: False)
    monkeypatch.setattr(HK, "default_interpret", lambda: False)
    monkeypatch.setattr(EK, "default_interpret", lambda: False)


@pytest.fixture(scope="module")
def model():
    return build(get_config("gboard-cifg-lstm").with_(cell_path="fused"))


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _specs(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: _spec(sharding, s.shape, s.dtype), tree)


def _assert_compiled_pallas(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_dp_clip_sumsq(one_chip):
    _assert_compiled_pallas(lambda x: DK.sumsq(x, interpret=False),
                            _spec(one_chip, EMBED_TILES))


def test_dp_clip_accumulate_2d(one_chip):
    t = _spec(one_chip, EMBED_TILES)
    _assert_compiled_pallas(
        lambda a, d, f: DK.clip_accumulate_2d(a, d, f, interpret=False),
        t, t, _spec(one_chip, ()))


def _cell_args(sharding, lead=()):
    st = _spec(sharding, lead + (SLOTS, H))
    return (_spec(sharding, lead + (3, SLOTS, H)),
            _spec(sharding, (3, H, H), jnp.bfloat16), st, st)


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "chunk32"])
def test_cifg_cell_fwd(one_chip, vmapped):
    fn = lambda z, w, h, c: CK.cell_fwd(z, w, h, c, interpret=False)
    if vmapped:
        fn = jax.vmap(fn, in_axes=(0, None, 0, 0))
    lead = (CHUNK,) if vmapped else ()
    _assert_compiled_pallas(fn, *_cell_args(one_chip, lead))


@pytest.mark.parametrize("vmapped", [False, True], ids=["one", "chunk32"])
def test_cifg_cell_bwd(one_chip, vmapped):
    fn = lambda z, w, h, c, dh, dc: CK.cell_bwd(z, w, h, c, dh, dc,
                                                interpret=False)
    if vmapped:
        fn = jax.vmap(fn, in_axes=(0, None, 0, 0, 0, 0))
    lead = (CHUNK,) if vmapped else ()
    st = _spec(one_chip, lead + (SLOTS, H))
    _assert_compiled_pallas(fn, *_cell_args(one_chip, lead), st, st)


def _sequence(zx, w_h):
    h0 = jnp.zeros((zx.shape[1], H), jnp.float32)
    return CO.cifg_sequence(zx, h0, h0, w_h, cell="fused",
                            compute_dtype=jnp.bfloat16, interpret=False)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_cifg_sequence(one_chip, direction):
    fn = _sequence
    if direction == "bwd":
        fn = jax.grad(lambda zx, w: jnp.sum(_sequence(zx, w)[0] ** 2),
                      argnums=(0, 1))
    _assert_compiled_pallas(fn, _spec(one_chip, (SEQ, 10, 3 * H)),
                            _spec(one_chip, (H, 3 * H)))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_lm_head_xent(one_chip, direction):
    """The fused head's kernels at one client's 800 rows x 10,240 x 96,
    vmapped over a 25-client chunk with per-client tables."""
    rows = lambda dt=jnp.float32: _spec(one_chip, (HEAD_CHUNK, CLIENT_ROWS,
                                                   1), dt)
    args = [_spec(one_chip, (HEAD_CHUNK, CLIENT_ROWS, D), jnp.bfloat16),
            _spec(one_chip, (HEAD_CHUNK, VPAD, D)), rows(jnp.int32)]
    if direction == "fwd":
        fn = lambda y, w, l: HK.xent_fwd(y, w, l, vocab=VOCAB,
                                         row_block=CLIENT_ROWS,
                                         interpret=False)
    else:
        fn = lambda y, w, l, s, c: HK.xent_bwd(y, w, l, s, c, vocab=VOCAB,
                                               row_block=CLIENT_ROWS,
                                               interpret=False)
        args += [rows(), rows()]
    _assert_compiled_pallas(jax.vmap(fn), *args)


def _embed_vjp(lookup, cd=jnp.bfloat16):
    """The table's gradient of one client's lookup (800 rows, cotangent in
    ``cd``), vmapped over a 25-client chunk with per-client tables."""
    def one(table, tokens, ct):
        _, vjp = jax.vjp(lambda t: lookup(t, tokens, cd), table)
        return vjp(ct)[0]
    return jax.vmap(one)


def test_embed_grad(one_chip):
    _assert_compiled_pallas(
        _embed_vjp(partial(embed_lookup, interpret=False)),
        _spec(one_chip, (HEAD_CHUNK, VPAD, D)),
        _spec(one_chip, (HEAD_CHUNK, CLIENT_ROWS), jnp.int32),
        _spec(one_chip, (HEAD_CHUNK, CLIENT_ROWS, D), jnp.bfloat16))


@pytest.mark.parametrize("cd", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_embed_grad_on_chip(cd):
    """The compiled kernel's table gradient against the scatter of
    ``jnp.take``'s autodiff, on the chip at full width: Zipf-like ids
    (many repeats), every id of the table's last rows, and ids out of
    range."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs on a TPU only")
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    tokens = jnp.minimum(jax.random.pareto(k[0], 1.0, (HEAD_CHUNK,
                                                       CLIENT_ROWS)),
                         VPAD + 64).astype(jnp.int32) - 1
    tokens = tokens.at[:, :VPAD - VOCAB].set(jnp.arange(VOCAB, VPAD))
    tokens = tokens.at[:, -3:].set(jnp.array([VPAD, VPAD + 9, -VPAD - 1]))
    table = jax.random.normal(k[1], (HEAD_CHUNK, VPAD, D))
    ct = jax.random.normal(k[2], (HEAD_CHUNK, CLIENT_ROWS, D)).astype(cd)
    got = jax.jit(_embed_vjp(embed_lookup, cd))(table, tokens, ct)
    want = jax.jit(_embed_vjp(embed_lookup_ref, cd))(table, tokens, ct)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-6 * scale


def test_client_step(one_chip, on_chip, model):
    """The client's loss value-and-grad at batch 10, seq 16, bf16 compute."""
    params = _specs(one_chip, jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0)))
    tok = _spec(one_chip, (10, SEQ), jnp.int32)
    batch = {"tokens": tok, "labels": tok,
             "mask": _spec(one_chip, (10, SEQ))}
    _assert_compiled_pallas(jax.value_and_grad(model.loss_fn), params, batch)


def test_clip_accumulate_full_tree(one_chip, on_chip, model):
    params = _specs(one_chip, jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0)))
    _assert_compiled_pallas(
        lambda a, d, s: clip_accumulate_tree(a, d, 0.8, s,
                                             clip_path="fused"),
        params, params, _spec(one_chip, ()))


def test_decode_tick(one_chip, on_chip, model):
    params = _specs(one_chip, jax.eval_shape(model.init,
                                             jax.random.PRNGKey(0)))
    cache = _specs(one_chip, jax.eval_shape(
        lambda: model.init_cache(SLOTS, 64)))
    _assert_compiled_pallas(model.decode_step, params,
                            _spec(one_chip, (SLOTS,), jnp.int32), cache)

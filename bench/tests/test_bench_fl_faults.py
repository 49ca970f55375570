"""The training cells' comparison at a size a test run can hold, on the
CPU: the program passes it; the control (the plain reference in fp8, one
precision step below the configuration's bfloat16, in the program's place)
and each fault planted under a whole run (a step that returns its state
unchanged, half of the cohort left out with the mean taken over the rest,
the exchange between chips left out) fail it."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.drivers import fl
from bench.harness.compare import Checks
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]


def state_unchanged(engine):
    orig = engine._compute_phase

    def phase(params, opt_state, *args):
        _, _, rec = orig(params, opt_state, *args)
        return params, opt_state, rec

    engine._compute_phase = phase


def half_batch(engine):
    orig = engine._compute_phase
    keep = engine.cohort // 2
    engine._round_denom = keep

    def phase(params, opt_state, round_idx, batch_args, slot_mask,
              report_mask, corrupt, k_noise):
        report_mask = report_mask & (jnp.arange(report_mask.shape[0])
                                     < keep)
        return orig(params, opt_state, round_idx, batch_args, slot_mask,
                    report_mask, corrupt, k_noise)

    engine._compute_phase = phase


def no_exchange(engine):
    """Each chip folds what it holds and receives nothing from the
    others."""
    orig = engine._cohort_sums_from

    def sums(*args, **kw):
        real = jax.lax.all_gather

        def local(x, axis_name, **_):
            n = jax.lax.psum(1, axis_name)
            return jnp.broadcast_to(x[None], (n,) + x.shape)

        jax.lax.all_gather = local
        try:
            return orig(*args, **kw)
        finally:
            jax.lax.all_gather = real

    engine._cohort_sums_from = sums


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "no_exchange": no_exchange}


def checks_of(cfg, readings):
    c = Checks()
    for k, lim in cfg["limits"].items():
        c.add(k, readings[k], lim)
    return c


def test_program_passes_and_the_control_fails():
    cfg = tiny.fl_config()
    ctx = tiny.context(cfg, tiny.fl_mix())
    res = fl.run(ctx)
    assert res.checks.correct, res.checks.as_dict()
    assert res.failed == 0 and res.attempted >= 2
    assert res.record["compiles_in_window"] == 0
    s = fl.setup(ctx)
    ref = fl.reference(ctx, s)
    assert checks_of(cfg, fl.readings(s, fl.program_run(s), ref)).correct
    control = fl.readings(s, fl.reference(ctx, s, "fp8"), ref)
    assert not checks_of(cfg, control).correct, control


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(fault):
    ctx = tiny.context(tiny.fl_config(), tiny.fl_mix())
    res = fl.run(ctx, alter=FAULTS[fault])
    assert not res.checks.correct, res.checks.as_dict()


FOUR_CHIPS = r"""
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench.drivers import fl
from bench.tests import tiny
from bench.tests.test_bench_fl_faults import FAULTS
ctx = tiny.context(tiny.fl_config(), tiny.fl_mix(pods=2, shards=2),
                   devices=jax.devices()[:4])
alter = FAULTS[{fault!r}] if {fault!r} else None
print("CORRECT", fl.run(ctx, alter=alter).checks.correct)
"""


@pytest.mark.parametrize("fault,correct", [("", True),
                                           ("no_exchange", False)])
def test_four_chips_without_the_exchange_is_not_correct(fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIPS.format(root=str(ROOT), src=str(ROOT / "src"),
                             fault=fault)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert f"CORRECT {correct}" in p.stdout, p.stdout[-2000:]

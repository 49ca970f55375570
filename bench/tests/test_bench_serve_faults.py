"""The serving cell's comparison at a size a test run can hold, on the CPU:
the program passes it; the control (the plain reference in fp8 in the
program's place) and each fault planted under a whole run (a decode tick
that returns its state unchanged, half of the batch's rows left out, a
token or a candidate of the suggestion strip altered where it is produced)
fail it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import serve
from bench.tests import tiny


def state_unchanged(engine):
    orig = engine._tick_j

    def tick(p, cache, *args):
        before = jax.tree_util.tree_map(jnp.copy, cache)
        nxt, cands, _ = orig(p, cache, *args)
        return nxt, cands, before

    engine._tick_j = tick


def half_batch(engine):
    """Half of each tick's rows are left out, alternating from tick to tick
    so that every session meets it: their state goes in as zeros."""
    orig = engine._tick_j

    def tick(p, cache, *args):
        rows = jnp.arange(cache["h"].shape[0]) + engine.ticks
        odd = (rows % 2 == 1)[:, None]
        cache = dict(cache, h=jnp.where(odd, 0.0, cache["h"]),
                     c=jnp.where(odd, 0.0, cache["c"]))
        return orig(p, cache, *args)

    engine._tick_j = tick


def token_altered(engine):
    orig = engine._record_token

    def record(sess, tok, cands):
        if len(sess.tokens) == 2:
            tok = (tok + 1) % engine.vocab
        return orig(sess, tok, cands)

    engine._record_token = record


def candidate_altered(engine):
    """The second candidate of the strip is replaced where it is made."""
    orig = engine._record_token

    def record(sess, tok, cands):
        cands = np.array(cands)
        if len(sess.tokens) == 1:
            cands[1] = (cands[1] + 7) % engine.vocab
        return orig(sess, tok, cands)

    engine._record_token = record


def run(alter=None, keep=None):
    ctx = tiny.context(tiny.serve_config(), tiny.serve_mix(), seconds=1.0)
    return ctx, serve.run(ctx, alter=alter, keep=keep)


def test_program_passes_and_the_control_fails():
    keep = {}
    ctx, res = run(keep=keep)
    assert res.checks.correct, res.checks.as_dict()
    assert res.failed == 0 and res.attempted == 40
    assert res.record["compiles_in_window"] == 0
    control = serve.served_gaps(ctx, keep["params"], keep["sessions"],
                                keep["results"], precision="fp8")
    limits = ctx.config["limits"]
    assert any(control[k] > limits[k] for k in limits), control


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered, candidate_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered", "candidate_altered"])
def test_a_planted_fault_is_not_correct(fault):
    _, res = run(alter=fault)
    assert not res.checks.correct, res.checks.as_dict()

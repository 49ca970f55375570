"""The chip benchmark refuses to run without an accelerator: a CPU backend
or too few chips is a non-zero exit that names what JAX found, and no
result line."""
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from bench.harness.device import NoAccelerator, require_accelerator

ROOT = Path(__file__).resolve().parents[2]


def test_cpu_backend_is_refused_naming_the_platform():
    with pytest.raises(NoAccelerator, match="'cpu'") as e:
        require_accelerator(1, jax.devices("cpu"))
    assert e.value.code != 0


def test_too_few_chips_is_refused():
    fake = [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")]
    assert require_accelerator(1, fake) == fake
    with pytest.raises(NoAccelerator, match="asks for 4 chips"):
        require_accelerator(4, fake)


def test_run_exits_non_zero_and_prints_no_result_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "serve_typing", "--seed", str(2 ** 31 + 9), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "platform 'cpu'" in p.stderr

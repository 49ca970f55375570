"""The plumbing around chip runs: `chip_smoke.py` refuses to run without a
TPU, and the compilation cache is placed from outside the program."""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(SystemExit) as e:
        _load_chip_smoke().main([])
    assert e.value.code not in (0, None)
    assert "'cpu'" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after a test points it elsewhere."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_left_to_jax_when_env_set(monkeypatch, tmp_path,
                                        cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_in_checkout_when_env_unset(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    placed = compile_cache.enable_compile_cache()
    assert placed == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == placed
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored

"""CPU rehearsal of ``chip_smoke.py``: its phases at the 2-layer smoke size,
its refusal to run without a TPU, and where the launchers keep JAX's
persistent compile cache."""

import importlib.util
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_phases_pass_at_smoke_size(chip_smoke):
    result = chip_smoke.smoke(full=False, require_tpu=False)
    assert result["ok"] is True
    assert result["device"] == {"platform": "cpu", "kind": "cpu",
                                "count": len(jax.devices())}


def test_smoke_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU found" in err


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(cache_config, monkeypatch, env_dir):
    from repro.launch.serve import use_compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        # JAX reads the variable itself; the helper must not override it
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        jax.config.update("jax_compilation_cache_dir", env_dir)
        want = env_dir
    assert use_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want

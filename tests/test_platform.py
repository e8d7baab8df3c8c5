"""Device facts the program derives instead of taking as options: Pallas
interpret mode from the platform, peak rates from the device kind, and the
compilation-cache directory from the environment."""
import jax
import pytest

from repro.core.agcn import engine
from repro.core.agcn import model as M
from repro.configs import get_config
from repro.kernels import ops
from repro.launch import cache
from repro.launch.mesh import peak_rates


def test_interpret_mode_follows_platform():
    """The CPU interprets, a TPU compiles, anything else raises — and a
    Pallas plan built here records the CPU's answer."""
    assert ops.interpret_mode("cpu") is True
    assert ops.interpret_mode("tpu") is False
    with pytest.raises(ValueError, match="gpu"):
        ops.interpret_mode("gpu")
    assert ops.interpret_mode() is (jax.default_backend() == "cpu")
    cfg = get_config("agcn-2s", reduced=True)
    plan = engine.build_execution_plan(
        M.init_params(cfg, jax.random.PRNGKey(0)), cfg, backend="pallas")
    assert plan.static.interpret is ops.interpret_mode()


def test_peak_rates_refuse_unknown_device_kind():
    """v5e has its published peaks; a kind missing from the table raises
    instead of borrowing v5e's numbers."""
    v5e = peak_rates("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bw"] == 819e9
    for kind in ("TPU v4", "cpu", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            peak_rates(kind)


def test_compile_cache_dir_env_wins_else_fixed_repo_path(monkeypatch,
                                                          tmp_path):
    """$JAX_COMPILATION_CACHE_DIR is used as given; without it the cache
    sits at one fixed, git-ignored path inside the checkout."""
    monkeypatch.setenv(cache.ENV, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(cache.ENV)
    first = cache.compile_cache_dir()
    assert first == cache.compile_cache_dir()
    assert first.endswith(".jax_cache")
    root = cache.REPO_CACHE.parent
    assert (root / "src" / "repro" / "launch" / "cache.py").exists()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()

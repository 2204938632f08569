"""The launch entry points' shared pieces, and serving end to end on the CPU."""

import os

import jax
import numpy as np
import pytest

from repro.launch import common, serve

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_ignored_dir_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    common.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_env_var_sets_nothing(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", None)
    common.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("n, want", [(48, 48), (512, 512), (544, 1024), (1025, 1536)])
def test_cache_len_rounds_up_to_decode_block(n, want):
    assert serve.cache_len(n) == want


def test_model_config_cuts_depth_only():
    full = common.model_config("granite-moe-1b-a400m", full=True)
    cut = common.model_config("granite-moe-1b-a400m", full=True, layers=8)
    assert cut.n_layers == 8 and full.n_layers == 24
    for f in ("d_model", "n_heads", "n_kv_heads", "hd", "vocab_size", "dtype"):
        assert getattr(cut, f) == getattr(full, f)
    with pytest.raises(ValueError):
        common.model_config("granite-moe-1b-a400m", full=True, layers=25)


def test_model_config_cuts_granite_h_by_whole_periods():
    cut = common.model_config("granite-4.0-h-micro", full=True, layers=20)
    assert cut.layer_types == common.model_config("granite-4.0-h-micro", full=True).layer_types[:20]
    assert cut.layer_types.count("attention") == 2
    with pytest.raises(ValueError):
        common.model_config("granite-4.0-h-micro", full=True, layers=15)


def test_serve_kernel_path_matches_jnp_path():
    """Same seeded weights through publish, stage and restore; the Pallas
    path (interpret mode here) and the jnp path agree."""
    argv = ["--arch", "phi4-mini-3.8b", "--requests", "2", "--prompt-len", "16", "--gen", "4"]
    kern = serve.main([*argv, "--kernels"])
    ref = serve.main(argv)
    assert kern["generated"].shape == (2, 4)
    np.testing.assert_allclose(kern["first_logits"], ref["first_logits"], atol=1e-4)
    np.testing.assert_array_equal(kern["generated"], ref["generated"])

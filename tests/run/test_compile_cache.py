"""The persistent compile cache is placed from outside, or at one fixed
in-repo path — never at a temporary, pid- or time-based one — and its
keys do not depend on where the checkout sits."""
import re
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    jax.config.update("jax_hlo_source_file_canonicalization_regex", None)
    yield before
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", regex)


def test_env_var_wins_and_config_is_untouched(monkeypatch, tmp_path,
                                              cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == cache_config


def test_default_is_the_fixed_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    first = enable_compile_cache()
    assert first == str(REPO / ".jax_cache") == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert enable_compile_cache() == first      # stable across calls
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_host_only_process_keeps_no_cache(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == cache_config


@pytest.mark.parametrize("env_dir", [True, False])
def test_source_paths_are_relative_to_the_checkout(monkeypatch, tmp_path,
                                                   cache_config, env_dir):
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    enable_compile_cache()
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    kernel = "src/repro/kernels/adalomo_update/adalomo_update.py"
    assert re.sub(regex, "", str(REPO / kernel)) == kernel
    # a path outside the checkout is left as it is
    assert re.sub(regex, "", f"/elsewhere{REPO}/x.py") == f"/elsewhere{REPO}/x.py"


def test_a_source_regex_set_from_outside_is_kept(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_hlo_source_file_canonicalization_regex", "^/x/")
    enable_compile_cache()
    assert jax.config.jax_hlo_source_file_canonicalization_regex == "^/x/"

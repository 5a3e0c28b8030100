"""The entry points' persistent compile cache goes where the environment
says, and otherwise to one fixed directory of the checkout."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.jax_cache import REPO_ROOT, enable_compile_cache

KEYS = ("jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_config():
    was = {k: getattr(jax.config, k) for k in KEYS}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_cache_dir_from_env_or_checkout(env_dir, tmp_path, monkeypatch,
                                        restore_config):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO_ROOT, "artifacts", "jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert enable_compile_cache() == want
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == (
        want if env_dir is None else before)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert os.path.isfile(os.path.join(REPO_ROOT, "chip_smoke.py"))

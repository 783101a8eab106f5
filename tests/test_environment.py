"""Environment contract: tests and benches must see exactly ONE device —
the 512-fake-device flag belongs to the dry-run alone (its module sets
XLA_FLAGS before any jax import; see repro/launch/dryrun.py)."""
import os

import jax


def test_tests_see_one_device():
    assert "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
    assert jax.device_count() == 1


def test_dryrun_sets_flag_first():
    """The dry-run module's first statements must pin the device count."""
    import inspect

    import repro.launch.dryrun as dr

    src = inspect.getsource(dr).splitlines()
    head = "\n".join(src[:3])
    assert "xla_force_host_platform_device_count=512" in head


def _restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache

    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)

    def restore():
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])
        compilation_cache.reset_cache()

    return restore


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """Without JAX_COMPILATION_CACHE_DIR the cache is the checkout's fixed
    ``.jax_cache`` — never a temporary or per-run path."""
    from repro.common.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    restore = _restore_cache_config()
    try:
        path = enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert enable_compile_cache() == path          # stable across calls
    finally:
        restore()


def test_compile_cache_env_dir_is_the_only_one(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands and the
    code configures no other directory."""
    from repro.common.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    restore = _restore_cache_config()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
    finally:
        restore()

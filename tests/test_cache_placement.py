"""Where the persistent compile cache goes (presto_tpu/__init__.py):
JAX_COMPILATION_CACHE_DIR alone when set, else <checkout>/.jax_cache
on a non-CPU platform, and none for a CPU run of its own."""

import importlib.util

import pytest

import jax

import presto_tpu


@pytest.mark.parametrize("platforms,libtpu,want", [
    ("cpu", True, False),
    ("CPU,tpu", True, False),
    ("tpu,cpu", False, True),
    ("cuda", False, True),
    (None, True, True),
    (None, False, False),
])
def test_accelerator_run(monkeypatch, platforms, libtpu, want):
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    real = importlib.util.find_spec
    monkeypatch.setattr(
        importlib.util, "find_spec",
        lambda name, *a: (object() if libtpu else None)
        if name == "libtpu" else real(name, *a))
    assert presto_tpu._accelerator_run() is want


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)


def test_cache_in_checkout_when_unset(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    jax.config.update("jax_compilation_cache_dir", None)
    presto_tpu._enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == presto_tpu.CACHE_DIR
    assert presto_tpu.CACHE_DIR.endswith("/.jax_cache")


def test_env_dir_is_the_only_cache(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    jax.config.update("jax_compilation_cache_dir", None)
    presto_tpu._enable_compilation_cache()
    # JAX reads the variable itself; the package sets no directory
    assert jax.config.jax_compilation_cache_dir is None


def test_cpu_run_keeps_no_cache(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    jax.config.update("jax_compilation_cache_dir", None)
    presto_tpu._enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir is None

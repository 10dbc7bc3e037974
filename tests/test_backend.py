"""utils/backend.py: which backend counts as a TPU, and who places the
XLA compile cache (environment, then config, then a fixed path on a
TPU), plus the one switch that turns the Pallas extract on."""

import os

import jax
import pytest

from veneur_tpu.utils import backend


@pytest.fixture
def cache_setting():
    """Restore jax's cache dir after a test that lets code set it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_is_tpu_backend_is_the_default_backend(monkeypatch):
    assert backend.is_tpu_backend() == (jax.default_backend() == "tpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert backend.is_tpu_backend()
    # a second registration name is not a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "not-a-tpu")
    assert not backend.is_tpu_backend()


def test_environment_places_the_cache_and_code_sets_nothing(
        monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    got = backend.place_compilation_cache("/somewhere/else")
    assert got == str(tmp_path)
    assert calls == []


def test_config_places_the_cache_when_the_environment_is_silent(
        monkeypatch, tmp_path, cache_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = backend.place_compilation_cache(str(tmp_path))
    assert got == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


@pytest.mark.parametrize("on_tpu", [False, True])
def test_default_cache_is_a_fixed_path_and_only_on_a_tpu(
        monkeypatch, cache_setting, on_tpu):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(backend, "is_tpu_backend", lambda: on_tpu)
    got = backend.place_compilation_cache("")
    if on_tpu:
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        assert got == ""


def test_server_places_the_cache_through_the_resolver(monkeypatch, tmp_path):
    from veneur_tpu.core.config import Config
    from veneur_tpu.core.server import Server

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    srv = Server(Config(tpu_compilation_cache_dir="/ignored/by/env",
                        tpu_native_ingest=False))
    try:
        assert srv.compilation_cache_dir == str(tmp_path)
    finally:
        srv.shutdown()

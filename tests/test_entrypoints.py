"""Entry-point contracts: no silent CPU fallback, honest exit codes, and
where compiled programs are cached."""
import os

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.core import BatchedExtractor, dispatcher
from repro.core.executor import PlanExecutor
from repro.launch import serve
from repro.runtime import compile_cache

SERVE_ARGS = ["--backend", "ref", "--clients", "1", "--requests", "2",
              "--huge-every", "0"]


@pytest.mark.skipif(dispatcher.has_tpu(), reason="this host has a TPU")
def test_pallas_backend_refuses_a_host_without_tpu():
    with pytest.raises(RuntimeError, match="needs a TPU|for a TPU"):
        dispatcher.resolve_backend("pallas")
    with pytest.raises(RuntimeError, match="for a TPU"):
        BatchedExtractor(backend="pallas")


@pytest.fixture
def restore_cache_config():
    """Undo the cache settings, and JAX's once-per-process decision to use
    the cache, so later tests on this worker compile without it."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    old = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.fixture
def no_compile_cache(monkeypatch):
    """serve.main compiles: keep its programs out of the checkout's cache."""
    monkeypatch.setattr(serve, "use_compile_cache", lambda: None)


def test_serve_exits_zero_when_every_request_is_answered(
        capsys, no_compile_cache):
    assert serve.main(SERVE_ARGS) == 0
    assert "FAILED" not in capsys.readouterr().out


def test_serve_exits_nonzero_when_a_window_dies(monkeypatch, capsys,
                                               no_compile_cache):
    def broken(self, window):
        raise RuntimeError("device lost")

    monkeypatch.setattr(PlanExecutor, "collect_window", broken)
    assert serve.main(SERVE_ARGS) == 1
    out = capsys.readouterr().out
    assert "FAILED" in out and "WindowFailed: RuntimeError: device lost" in out


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_leaves_the_environment_variable_to_jax(
        monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # set nothing

"""Launcher policy: where the persistent compilation cache goes (the
environment variable wins, otherwise a fixed directory inside the
checkout), and which backends a TPU host may serve on."""
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache, serve

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_fixed_and_inside_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache() \
        == str(REPO / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2


@pytest.mark.parametrize("backend", serve.CHILD_PROCESS_BACKENDS)
def test_serve_refuses_child_process_backends_on_tpu(monkeypatch, capsys,
                                                     backend):
    """On a TPU host the launcher's process holds the chip, so a backend
    whose workers are child processes is refused before anything runs."""
    monkeypatch.setattr(serve.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sys, "argv", ["serve", "--smoke",
                                      "--backend", backend])
    with pytest.raises(SystemExit) as e:
        serve.main()
    assert e.value.code == 2
    assert "holds the chip" in capsys.readouterr().err

"""Nothing on the engine path may hide which device a run used, and the
compile cache must be placeable from outside."""

import os

import jax
import pytest

from colearn_federated_learning_tpu.fed import engine
from colearn_federated_learning_tpu.ops import attention
from colearn_federated_learning_tpu.utils import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- compile cache placement ---------------------------------------------
def test_compile_cache_placed_from_outside(tmp_path, monkeypatch):
    # The variable wins (jax itself read it at import), and no directory
    # is assigned in code.
    placed = str(tmp_path / "outside")
    monkeypatch.setenv(compile_cache.ENV_VAR, placed)
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a, **k: pytest.fail(f"jax.config.update{a} in code"))
    assert compile_cache.enable_compile_cache() == placed
    assert os.environ[compile_cache.ENV_VAR] == placed


def test_compile_cache_default_is_fixed_path_in_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR)
    told = {}
    monkeypatch.setattr(jax.config, "update", told.__setitem__)
    first = compile_cache.enable_compile_cache()
    assert first == os.path.join(_ROOT, ".jax_cache", compile_cache.host_key())
    # jax is told, and so are child processes; a second call agrees.
    assert told == {"jax_compilation_cache_dir": first}
    assert os.environ[compile_cache.ENV_VAR] == first
    assert compile_cache.enable_compile_cache() == first
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.enable_compile_cache() == first


# ---- device selection ------------------------------------------------------
def test_resolve_devices_auto_reraises_backend_failure(monkeypatch):
    def dead_backend(*a):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", dead_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        engine._resolve_devices("auto")


def test_resolve_devices_tpu_raises_on_cpu_process():
    assert engine._resolve_devices("auto") == jax.devices()
    with pytest.raises(RuntimeError, match="no accelerator"):
        engine._resolve_devices("tpu")
    with pytest.raises(ValueError, match="unknown backend"):
        engine._resolve_devices("gpu")


def test_unparseable_tpu_device_kind_is_an_error(monkeypatch):
    class Odd:
        device_kind = "TPU lite"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [Odd()])
    attention._tpu_generation.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="TPU generation"):
            attention._tpu_generation()
        Odd.device_kind = "TPU v5 lite"
        assert attention._tpu_generation() == 5
    finally:
        attention._tpu_generation.cache_clear()

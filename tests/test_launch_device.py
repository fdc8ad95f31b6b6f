"""repro.launch.device: the compile cache's directory and the device-count
error, and the chip lookup by device kind."""
import jax
import pytest

from conftest import REPO
from repro.core.hardware import TPU_V5E, chip_for_kind
from repro.launch import device


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_uses_the_environment_directory(monkeypatch, tmp_path,
                                                      cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.enable_compile_cache()
    assert path == str(device.CACHE_DIR) == jax.config.jax_compilation_cache_dir
    assert device.CACHE_DIR.parent == REPO
    assert device.enable_compile_cache() == path  # stable across calls


def test_take_devices_returns_the_first_n():
    assert device.take_devices(2, "test") == jax.devices()[:2]


def test_take_devices_gives_xla_flags_advice_only_on_cpu(monkeypatch):
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        device.take_devices(len(jax.devices()) + 1, "dp=9")

    class Tpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Tpu()])
    with pytest.raises(RuntimeError) as e:
        device.take_devices(4, "dp=4")
    assert "1 tpu device(s)" in str(e.value)
    assert "XLA_FLAGS" not in str(e.value)


def test_chip_for_kind():
    assert chip_for_kind("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="TPU v9"):
        chip_for_kind("TPU v9")

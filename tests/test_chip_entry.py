"""What decides where the serving plane runs, checked on the CPU: the served
config per platform, the chip peaks table, the compile-cache directory, the
``tpu-v5e`` provider and ``chip_smoke.py``'s device check. No test here
starts a process that loads JAX."""
import importlib.util
import tempfile
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.configs import get_config, reduced, served_config, served_cut
from repro.launch import compile_cache
from repro.launch.mesh import chip_peaks

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_served_config_is_reduced_on_cpu_only():
    assert served_config("yi-9b", "cpu") == reduced(get_config("yi-9b"))
    full, cut = get_config("yi-9b"), served_config("yi-9b", "tpu")
    assert cut.num_layers == 24 and full.num_layers == 48
    for width in ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "dtype"):
        assert getattr(cut, width) == getattr(full, width), width
    assert set(served_cut("yi-9b").reduced) == {"num_layers"}


def test_arch_without_a_cut_is_refused_off_cpu():
    with pytest.raises(ValueError, match="no one-chip serving cut"):
        served_config("qwen2-72b", "tpu")


def test_chip_peaks_are_keyed_by_device_kind():
    assert chip_peaks("TPU v5 lite").flops_bf16 == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("cpu")


def test_compile_cache_dir_follows_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.compile_cache_dir() == "/elsewhere/cache"


def test_compile_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(ROOT / ".xla_cache")
    assert compile_cache.compile_cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.configure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_tpu_provider_refuses_cpu_devices():
    import repro.core.services  # noqa: F401
    from repro.core.vre import VREConfig, VirtualResearchEnvironment
    vre = VirtualResearchEnvironment(VREConfig(
        name="tpu-on-cpu", services=["lm-server"], arch="yi-9b",
        provider="tpu-v5e", workdir=tempfile.mkdtemp()))
    with pytest.raises(RuntimeError, match="tpu"):
        vre.instantiate()


def test_chip_smoke_refuses_cpu(chip_smoke):
    with pytest.raises(SystemExit) as e:
        chip_smoke.require_tpu(jax.devices())
    assert e.value.code not in (0, None)


def test_chip_smoke_device_record(chip_smoke):
    chip = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert chip_smoke.require_tpu([chip]) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    with pytest.raises(SystemExit):
        chip_smoke.require_tpu([chip], count=4)

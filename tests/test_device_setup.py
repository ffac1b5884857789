"""What the chip entry points rely on: the persistent compilation cache
helper and the table of published device peaks."""

import jax
import pytest

from repro.core.formats import SSTGeometry
from repro.launch import compile_cache
from repro.lsm import cpu_engine
from repro.roofline import constants


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_repo_dir_without_env(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    # the same directory on every call: a fresh directory starts empty
    assert compile_cache.enable_compile_cache() == got
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "chip_smoke.py").exists()


def test_v5e_peaks_are_the_published_ones():
    chip = constants.peaks("TPU v5 lite")
    assert (chip.bf16_flops, chip.hbm_bw, chip.ici_link_bw) == (
        197e12, 819e9, 50e9)
    assert "TPU v5e" in chip.source
    # no TPU here: models describe the v5e
    assert constants.peaks() is chip


def test_unlisted_tpu_kind_raises(monkeypatch):
    with pytest.raises(KeyError, match="TPU v99"):
        constants.peaks("TPU v99")
    monkeypatch.setattr(constants, "_default_kind", lambda: "TPU v99")
    geom = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                       sst_bytes=2048)
    with pytest.raises(KeyError, match="TPU v99"):
        cpu_engine.model_device_seconds(4096, 4096, geom)


def test_model_device_seconds_reads_the_table():
    geom = SSTGeometry(key_bytes=16, value_bytes=32, block_bytes=512,
                       sst_bytes=2048)
    chip = constants.peaks("TPU v5 lite")
    n = 1 << 20
    want = (3 * 2 * n / chip.hbm_bw + 2 * n / constants.HOST_LINK_BW_ASSUMED
            + 20e-6)
    assert cpu_engine.model_device_seconds(n, n, geom) == pytest.approx(want)


@pytest.mark.parametrize("restart", [0, 24, 256])
def test_geometry_rejects_restart_intervals_the_kernel_cannot_tile(restart):
    # both engines refuse the geometry up front, not at the first flush
    with pytest.raises(ValueError, match="must divide 128"):
        SSTGeometry(restart_interval=restart)

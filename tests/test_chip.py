"""Where the program runs: the compile-cache location, the one-process-per-
chip rule, and that a TPU backend never runs a kernel in interpret mode.

No test here touches a TPU: TPU hosts and backends are stood in for with
monkeypatch.
"""
import os

import jax
import pytest

from repro import chip
from repro.fleet import SweepSpec, spawn_workers
from repro.kernels import ops


@pytest.fixture
def cache_dir_config():
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    chip.enable_compile_cache()
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert chip.CACHE_DIR == os.path.join(checkout, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == chip.CACHE_DIR


def test_compile_cache_env_variable_stands(monkeypatch, cache_dir_config,
                                           tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    chip.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_cpu_platform_is_not_a_tpu_host(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert not chip.tpu_host()
    chip.check_local_workers(4)          # allowed off-TPU


@pytest.mark.parametrize("workers,refused", [(1, False), (2, True)])
def test_local_workers_on_a_tpu_host(monkeypatch, workers, refused):
    monkeypatch.setattr(chip, "tpu_host", lambda: True)
    if refused:
        with pytest.raises(RuntimeError, match="one process"):
            chip.check_local_workers(workers)
    else:
        chip.check_local_workers(workers)


def test_spawn_workers_refuses_before_starting(monkeypatch, tmp_path):
    from repro.configs import SwarmConfig
    monkeypatch.setattr(chip, "tpu_host", lambda: True)
    spec = SweepSpec.build("refused", SwarmConfig(), strategies=(0,),
                           num_runs=1)
    with pytest.raises(RuntimeError, match="TPU host"):
        spawn_workers(spec, str(tmp_path), 2)
    assert not any(tmp_path.iterdir())


def test_tpu_backend_ignores_force_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "1")
    assert ops._mode() == "interpret"            # off-TPU: honoured
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._mode() == "tpu"

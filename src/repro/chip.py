"""Where the program runs: its compile cache and the one-process-per-chip
rule.

Every entry point (``chip_smoke.py``, ``benchmarks/run.py``, the figure
sweeps, ``examples/*``, ``repro.launch.*``) calls
:func:`enable_compile_cache` before its first compile.

A TPU chip belongs to one process at a time, and a process that has
initialized a JAX backend holds its chips until it exits.  So code that
spawns workers asks :func:`tpu_host` — which reads the PCI bus and the
``JAX_PLATFORMS`` variable and initializes no backend — and refuses to
start more than one local worker on a TPU host; several chips are driven
from one process through the ``sharded`` executor backend.
"""
from __future__ import annotations

import os

import jax

#: the checkout this module belongs to (``<checkout>/src/repro/chip.py``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> None:
    """Keep JAX's persistent compilation cache at ``<checkout>/.jax_cache``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  The path is fixed because it is part of what the
    cache matches on: a directory that moves never hits.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)


def tpu_host() -> bool:
    """True when JAX in this process would take TPU chips, decided without
    initializing a backend (which would claim them)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return False
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0] > 0


def check_local_workers(workers: int) -> None:
    """Refuse ``workers > 1`` local processes on a TPU host, before any is
    started: each would need the chips that the first one holds, and would
    fail or hang."""
    if workers > 1 and tpu_host():
        raise RuntimeError(
            f"{workers} local worker processes requested on a TPU host: a "
            "chip belongs to one process at a time, so run one process "
            "(workers=1) and use the 'sharded' backend to drive several "
            "chips from it")

"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed with jax, compiles for a chip
that is described and not attached, and refuses what the chip would refuse
(block shapes that break the tiling rules, gathers the kernel language
lacks, programs that do not fit).  Interpret mode shows none of that.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import SwarmConfig, get_config
from repro.kernels.decode_attention import decode_attention
from repro.kernels.diffusive_phi import diffusive_phi, diffusive_phi_sparse
from repro.kernels.flash_attention import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


F32, I32, BF16 = jnp.float32, jnp.int32, jnp.bfloat16


@pytest.mark.parametrize("R", [1, 4])
def test_dense_phi_compiles(one_chip, R):
    N = 4096
    c = _compile(diffusive_phi, one_chip, ((R, N), F32), ((R, N), F32),
                 ((R, N, N), F32))
    assert _has_kernel(c)


@pytest.mark.parametrize("R,K", [(1, 16), (4, 16), (2, 200)])
def test_sparse_phi_compiles(one_chip, R, K):
    N = 4096
    c = _compile(diffusive_phi_sparse, one_chip, ((R, N), F32),
                 ((R, N), F32), ((R, N, K), F32), ((R, N, K), I32))
    assert _has_kernel(c)


QWEN = get_config("qwen3-1.7b")


def test_flash_attention_compiles_at_qwen3_widths(one_chip):
    B, S = 4, 512
    q = ((B, S, QWEN.num_heads, QWEN.head_dim_), BF16)
    kv = ((B, S, QWEN.num_kv_heads, QWEN.head_dim_), BF16)
    c = _compile(flash_attention, one_chip, q, kv, kv)
    assert _has_kernel(c)


def test_decode_attention_compiles_at_qwen3_widths(one_chip):
    B, S = 4, 1024
    q = ((B, QWEN.num_heads, QWEN.head_dim_), BF16)
    kv = ((B, S, QWEN.num_kv_heads, QWEN.head_dim_), BF16)
    c = _compile(decode_attention, one_chip, q, kv, kv, ((), I32))
    assert _has_kernel(c)


def test_run_sim_paper_defaults_compiles_with_phi_kernel(one_chip,
                                                         monkeypatch):
    """The whole vmapped simulator at Table 2 defaults, with the φ update
    forced onto the kernel path (this process's backend is the CPU)."""
    from repro.kernels import ops
    from repro.swarm.simulator import run_sim
    monkeypatch.setattr(ops, "_mode", lambda: "tpu")
    cfg = SwarmConfig()

    def fn(key, strategy):
        keys = jax.random.split(key, cfg.num_runs)
        return jax.vmap(functools.partial(run_sim, cfg=cfg, strategy=strategy,
                                          n=cfg.num_workers))(keys)

    c = _compile(fn, one_chip, ((2,), jnp.uint32), ((), I32))
    assert _has_kernel(c)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "lists"])
def test_only_list_delivery_compiles_to_serial_loops(one_chip, dense):
    """The TPU compiler lowers a scatter or gather over the batched node
    axis to a serial loop, a ``kCustom`` fusion.  One tick of transfer
    progress, vmapped over Table 2's runs: delivery through the dense
    one-hot has none; over neighbour lists it keeps its scatters."""
    from repro.swarm.simulator import init_state
    from repro.swarm.transfer import progress
    cfg = SwarmConfig()
    n, runs = cfg.num_workers, cfg.num_runs

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    st = jax.eval_shape(jax.vmap(lambda k: init_state(k, cfg, n)),
                        jax.random.split(jax.random.PRNGKey(0), runs))
    fn = jax.vmap(lambda s, r, a: progress(s, r, a, cfg, jnp.float32(0.5),
                                           dense=dense))
    c = jax.jit(fn).lower(jax.tree.map(on_chip, st),
                          on_chip(jax.ShapeDtypeStruct((runs, n), F32)),
                          on_chip(jax.ShapeDtypeStruct((runs, n), bool))
                          ).compile()
    assert ("kind=kCustom" in c.as_text()) != dense

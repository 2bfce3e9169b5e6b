"""Traced-program registry for the jaxpr tier (DESIGN.md §15.1).

A *target* names one real program the repo compiles and a zero-argument
builder that returns ``(fn, example_args)`` suitable for
``jax.make_jaxpr``.  The rules never construct programs themselves — they
lint whatever this registry traces, so adding a subsystem here
automatically puts it under J001–J004.

Covered surface (mirrors how the programs are actually built):

* ``sim_*`` — ``run_sim`` end to end: the dense path, the sparse
  neighbor-list path (DESIGN.md §11), the fully-traced path (task + hop +
  state streams, §10/§12), and scenario-registry combinations (stochastic
  channel / mobility / fault entries), each with the strategy id left
  traced exactly as the executors trace it;
* ``kernel_*`` — the φ kernel dispatchers in ``repro.kernels.ops``
  (dense and sparse), traced through the same dispatch path the
  simulator uses;
* ``executor_*`` — the three fleet backends' batched programs (vmap /
  streaming ``lax.map`` / ``shard_map`` over a 1-device mesh), built the
  same way ``fleet.executor`` builds them, minus the AOT compile;
* ``serve_congestion_core`` — the jitted numerics of
  ``SplitServeEngine.step`` (congestion EMA → exit labels,
  ``repro.core.early_exit``).  The engine's step loop itself is host-side
  python over deques — there is no whole-step jaxpr to lint; its traced
  surface *is* this core (see DESIGN.md §15.1).

Targets are deliberately small (N = 13, one simulated second): jaxpr
structure does not depend on array sizes, and the distinctive prime N
lets rules identify the cross-node axis by dimension.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from repro.analysis.jaxpr.jaxpr_util import HAVE_JAX, trace32_64

#: the distinctive swarm size rules use to recognize the N axis
TARGET_N = 13
#: simulated seconds per target — two epochs at the default period
TARGET_SIM_S = 1.0


@dataclass(frozen=True)
class Target:
    name: str
    kind: str                       # sim | kernel | executor | serve
    build: Callable[[], Tuple[Callable, tuple]]
    n_axis: Optional[int] = TARGET_N   # None: no cross-node axis to audit


class TracedTarget:
    """One target's traced programs: x32 always, x64 pair for J002."""

    def __init__(self, target: Target, jaxpr32, jaxpr64, err64):
        self.target = target
        self.name = target.name
        self.n_axis = target.n_axis
        self.jaxpr32 = jaxpr32
        self.jaxpr64 = jaxpr64
        self.err64 = err64


def _sim_cfg(**over):
    from repro.configs.base import SwarmConfig
    return SwarmConfig(num_workers=TARGET_N, sim_time_s=TARGET_SIM_S,
                       **over)


def _sim_builder(**over):
    def build():
        import jax
        import jax.numpy as jnp

        from repro.swarm.simulator import run_sim
        cfg = _sim_cfg(**over)

        def fn(key, strategy):
            return run_sim(key, cfg, strategy, TARGET_N)
        return fn, (jax.random.PRNGKey(0), jnp.int32(4))
    return build


def _kernel_dense():
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import diffusive_phi
    n = TARGET_N
    k = jax.random.PRNGKey(0)
    inv_phi = jax.random.uniform(k, (n,), jnp.float32, 0.5, 1.5)
    F = jnp.ones((n,), jnp.float32)
    d_tx = jnp.ones((n, n), jnp.float32)

    def fn(inv_phi, F, d_tx):
        return diffusive_phi(inv_phi, F, d_tx)
    return fn, (inv_phi, F, d_tx)


def _kernel_sparse():
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import diffusive_phi_sparse
    n, K = TARGET_N, 4
    k = jax.random.PRNGKey(0)
    # sparse kernel contract is batched: [R, N] / [R, N, K] (kernels/ref.py)
    inv_phi = jax.random.uniform(k, (1, n), jnp.float32, 0.5, 1.5)
    F = jnp.ones((1, n), jnp.float32)
    nbr = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[None, None, :],
                           (1, n, K))
    d_tx_e = jnp.ones((1, n, K), jnp.float32)

    def fn(inv_phi, F, d_tx_e, nbr):
        return diffusive_phi_sparse(inv_phi, F, d_tx_e, nbr)
    return fn, (inv_phi, F, d_tx_e, nbr)


def _executor_vmap():
    import jax
    import jax.numpy as jnp

    from repro.swarm.simulator import run_sim
    cfg = _sim_cfg()
    num_runs = 3

    def fn(key, strategy):
        keys = jax.random.split(key, num_runs)
        return jax.vmap(lambda k: run_sim(k, cfg, strategy, TARGET_N))(keys)
    return fn, (jax.random.PRNGKey(0), jnp.int32(4))


def _executor_streaming():
    import jax
    import jax.numpy as jnp

    from repro.swarm.simulator import run_sim
    cfg = _sim_cfg()
    chunk = 2

    def fn(keys, strategy):
        return jax.lax.map(lambda k: run_sim(k, cfg, strategy, TARGET_N),
                           keys)
    keys = jax.random.split(jax.random.PRNGKey(0), chunk)
    return fn, (keys, jnp.int32(4))


def _executor_sharded():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.swarm.simulator import run_sim
    cfg = _sim_cfg()
    devs = np.asarray(jax.devices())
    mesh = Mesh(devs, ("mc",))
    padded = len(devs)

    def fn(keys, strategy):
        return jax.shard_map(
            lambda ks: jax.vmap(
                lambda k: run_sim(k, cfg, strategy, TARGET_N))(ks),
            mesh=mesh, in_specs=P("mc"), out_specs=P("mc"),
            check_vma=False)(keys)
    keys = jax.random.split(jax.random.PRNGKey(0), padded)
    return fn, (keys, jnp.int32(4))


def _serve_congestion_core():
    import jax.numpy as jnp

    from repro.core.early_exit import (CongestionState, congestion_update,
                                       exit_label)
    n_stages = 4

    def fn(prev_T, prev_D, qlens):
        state = congestion_update(CongestionState(prev_T, prev_D), qlens,
                                  dt=0.01, alpha=0.3)
        return state.prev_T, state.D, exit_label(state.D, 1.5, 2.5)
    z = jnp.zeros((n_stages,), jnp.float32)
    return fn, (z, z, z)


def all_targets() -> List[Target]:
    return [
        Target("sim_dense", "sim", _sim_builder()),
        Target("sim_sparse", "sim",
               _sim_builder(neighbor_mode="sparse", neighbor_k=4)),
        Target("sim_traced", "sim",
               _sim_builder(trace_capacity=64, trace_hop_capacity=64,
                            trace_state_every=2)),
        Target("sim_scenario_stochastic", "sim",
               _sim_builder(channel_model="log_normal_corr",
                            mobility_model="gauss_markov",
                            fault_model="markov")),
        Target("sim_scenario_fading", "sim",
               _sim_builder(channel_model="rician",
                            mobility_model="levy_flight")),
        Target("kernel_phi_dense", "kernel", _kernel_dense),
        Target("kernel_phi_sparse", "kernel", _kernel_sparse),
        # n_axis=None: the executor targets audit the *batching wrappers*
        # (dtype drift, closure consts, fingerprints); the cross-node-axis
        # scan audit runs on the sim targets, which trace the same body.
        # The streaming backend in particular lowers lax.map to a scan
        # over the Monte-Carlo axis, which would wrap even `summarize` in
        # a scan context and turn J001 into noise.
        Target("executor_vmap", "executor", _executor_vmap, n_axis=None),
        Target("executor_streaming", "executor", _executor_streaming,
               n_axis=None),
        Target("executor_sharded", "executor", _executor_sharded,
               n_axis=None),
        Target("serve_congestion_core", "serve", _serve_congestion_core,
               n_axis=None),
    ]


def trace_targets(targets: Optional[List[Target]] = None
                  ) -> Dict[str, TracedTarget]:
    """Trace every target once (x32 + x64); shared across all J rules."""
    if not HAVE_JAX:                                 # pragma: no cover
        return {}
    out: Dict[str, TracedTarget] = {}
    for t in (all_targets() if targets is None else targets):
        fn, args = t.build()
        j32, j64, err = trace32_64(fn, *args)
        out[t.name] = TracedTarget(t, j32, j64, err)
    return out

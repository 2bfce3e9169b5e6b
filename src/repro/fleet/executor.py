"""Execution backends for Monte-Carlo sweep points (DESIGN.md §8.2).

All three backends batch the *seed* axis of one :class:`SweepPoint` around
the single-simulation ``run_sim`` and are bit-identical on equal
``(cfg, strategy, n, num_runs, seed)`` — proven by tests — so the choice is
purely operational:

  * ``vmap``      — one fused executable over all runs on one device; the
                    default, and exactly the historical ``run_many`` path
                    (``swarm.run_many`` routes here, so the simulator and
                    the benchmarks share this batching code).
  * ``sharded``   — ``jax.shard_map`` over a 1-D ``("mc",)`` device mesh:
                    each device vmaps its slice of the run axis.  Run
                    count is padded up to the device count by repeating
                    the last key (padding is computed then
                    discarded — never over-split the key, key-prefix
                    stability does not hold across split widths).
  * ``streaming`` — a host loop over fixed-size chunks; inside a chunk
                    ``jax.lax.map`` runs simulations *serially* with the
                    chunk key buffer donated, so peak memory is one swarm
                    state + the per-run summary rows regardless of N or run
                    count (the N ≥ 1k regime).  With a store attached, each
                    completed chunk checkpoints, and a killed sweep resumes
                    at the last completed chunk.

Strategy ids stay *traced* scalars (one executable covers all five
strategies per cfg), configs stay static — identical compile economics to
the simulator itself.

Per-task and per-hop telemetry (DESIGN.md §10) ride through every backend
unchanged: a traced config (``trace_capacity > 0`` and/or
``trace_hop_capacity > 0``) adds ``trace_records`` / ``trace_overflow``
(and ``trace_hops`` / ``trace_hop_overflow``) leaves to the metric dict,
which vmap/shard_map batch over the run axis and the streaming loop
concatenates per chunk — so record buffers are bit-identical across
backends and survive the same chunk-level checkpoint resume as the
scalar metrics (tested in ``tests/test_trace.py`` /
``tests/test_hops.py``).  The state stream (``trace_state_every > 0``,
DESIGN.md §12) is three more such leaves, nothing backend-specific.

Self-profiling (DESIGN.md §12): every backend builds its executable
ahead-of-time (``jax.jit(fn).lower(...).compile()`` — same jaxpr and HLO
as dispatching through ``jit``, so numerics are bit-identical; pinned by
``tests/test_state_trace.py``), which splits the first-call wall clock
into an honest *compile* span and an *execute* span.  ``run_point``
surfaces them as ``_compile_s`` / ``_execute_s`` pseudo-metrics (leading
underscore: skipped by reports, never stored), which land in the
``point`` rows of progress.jsonl.  Executables are cached per (cfg, n,
run-shape) — cache hits repeat the original compile span, which is the
cost a cold worker would pay.

On a profiler trace (``jax.profiler``), each ``run_batch`` call is a step
span named ``run_batch`` whose ``step_num`` counts the calls of this
process, and a ``compile`` span inside it marks a real compile (an
executable-cache miss).  ``op_scopes`` maps the instructions of a cached
executable to the simulator phases they belong to (``repro.obs.scopes``),
the key for reading a device trace's op names by phase.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SwarmConfig
from repro.fleet.store import ResultStore, code_version, point_digest
from repro.fleet.sweep import SweepPoint, SweepSpec
from repro.obs import scopes
from repro.swarm.simulator import run_sim

BACKENDS = ("vmap", "sharded", "streaming")
DEFAULT_CHUNK = 8
# run_batch calls in this process: the step number of each one's span
_EXECUTIONS = itertools.count()


class SweepInterrupted(RuntimeError):
    """Raised by the streaming backend when ``max_chunks`` is reached —
    a deterministic stand-in for preemption in resume tests; progress up to
    the interrupt is checkpointed in the store."""


def _pad_keys(keys: jax.Array, to: int) -> jax.Array:
    pad = to - keys.shape[0]
    if pad <= 0:
        return keys
    return jnp.concatenate(
        [keys, jnp.broadcast_to(keys[-1:], (pad,) + keys.shape[1:])], axis=0)


# ---------------------------------------------------------------------------
# backends (each: key -> dict of [num_runs] metric arrays), built AOT so
# compile time and execute time are separable spans
# ---------------------------------------------------------------------------


def _key_struct() -> jax.ShapeDtypeStruct:
    k = jax.random.PRNGKey(0)
    return jax.ShapeDtypeStruct(k.shape, k.dtype)


_I32 = jax.ShapeDtypeStruct((), jnp.int32)


@functools.lru_cache(maxsize=None)
def _profiled_vmap(cfg: SwarmConfig, n: int, num_runs: int):
    """AOT executable for the vmap backend + its compile-span seconds."""
    def fn(key, strategy):
        with scopes.phase("init"):
            keys = jax.random.split(key, num_runs)
        return jax.vmap(lambda k: run_sim(k, cfg, strategy, n))(keys)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(scopes.COMPILE):
        compiled = jax.jit(fn).lower(_key_struct(), _I32).compile()
    return compiled, time.perf_counter() - t0


@functools.lru_cache(maxsize=None)
def _profiled_sharded(cfg: SwarmConfig, n: int, padded: int, mesh):
    """AOT executable for the sharded backend (padded key batch in)."""
    from jax.sharding import PartitionSpec as P

    def fn(keys, strategy):
        return jax.shard_map(
            lambda ks: jax.vmap(lambda k: run_sim(k, cfg, strategy, n))(ks),
            mesh=mesh, in_specs=P("mc"), out_specs=P("mc"),
            check_vma=False)(keys)
    ks = _key_struct()
    keys_struct = jax.ShapeDtypeStruct((padded,) + ks.shape, ks.dtype)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(scopes.COMPILE):
        compiled = jax.jit(fn).lower(keys_struct, _I32).compile()
    return compiled, time.perf_counter() - t0


@functools.lru_cache(maxsize=None)
def _profiled_stream(cfg: SwarmConfig, n: int, chunk: int, donate: bool):
    """AOT executable for one streaming chunk (lax.map, serial runs).

    ``donate`` releases the chunk key buffer where the runtime honors it
    (TPU/GPU — the memory-bounded regime streaming exists for); CPU XLA
    declines donation and would warn on every compile.
    """
    def fn(keys, strategy):
        return jax.lax.map(lambda k: run_sim(k, cfg, strategy, n), keys)
    ks = _key_struct()
    keys_struct = jax.ShapeDtypeStruct((chunk,) + ks.shape, ks.dtype)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(scopes.COMPILE):
        compiled = jax.jit(fn, donate_argnums=(0,) if donate else ()).lower(
            keys_struct, _I32).compile()
    return compiled, time.perf_counter() - t0


def _padded_runs(num_runs: int) -> int:
    """Run count of the sharded backend: padded up to the device count."""
    d = jax.device_count()
    return (num_runs + d - 1) // d * d


def _chunk(chunk_size: int, num_runs: int) -> int:
    return max(1, min(chunk_size, num_runs))


def _executable(cfg: SwarmConfig, n: int, num_runs: int, backend: str,
                chunk_size: int = DEFAULT_CHUNK):
    """The cached executable of one backend, and its compile seconds."""
    if backend == "vmap":
        return _profiled_vmap(cfg, n, num_runs)
    if backend == "sharded":
        from jax.sharding import Mesh
        mesh = Mesh(np.asarray(jax.devices()), ("mc",))
        return _profiled_sharded(cfg, n, _padded_runs(num_runs), mesh)
    if backend == "streaming":
        return _profiled_stream(cfg, n, _chunk(chunk_size, num_runs),
                                jax.default_backend() != "cpu")
    raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")


def op_scopes(cfg: SwarmConfig, n: int, num_runs: int,
              backend: str = "vmap") -> Dict[str, str]:
    """Instruction name (``fusion.780``) -> simulator phase of every op of
    the executable ``run_batch`` runs for these arguments that lies in a
    phase, read from its HLO text.  A device trace names its ops by these
    instruction names.  The executable comes from the cache, so nothing
    compiles twice."""
    compiled, _ = _executable(cfg, n, num_runs, backend)
    return scopes.op_scopes(compiled.as_text())


def _block(out):
    return jax.block_until_ready(out)


def _run_sharded(key, cfg: SwarmConfig, strategy, n: int, num_runs: int,
                 spans: Optional[Dict] = None):
    keys = _pad_keys(jax.random.split(key, num_runs), _padded_runs(num_runs))
    compiled, compile_s = _executable(cfg, n, num_runs, "sharded")
    t0 = time.perf_counter()
    out = _block(compiled(keys, jnp.asarray(strategy, jnp.int32)))
    if spans is not None:
        spans["_compile_s"] = compile_s
        spans["_execute_s"] = time.perf_counter() - t0
    return jax.tree.map(lambda x: x[:num_runs], out)


def _sys_gauges(sys_buf) -> Dict[str, float]:
    """Final-sample system gauges of a ``trace_state_sys`` buffer, run-mean,
    rounded — the live swarm-health row for progress.jsonl."""
    from repro.trace import schema
    s = np.asarray(sys_buf, np.float64)
    if s.ndim == 2:
        s = s[None]
    g = dict(zip(schema.SYS_GAUGES, s[:, -1, :].mean(axis=0), strict=True))
    return {"queue_depth_mean": round(g["queue_depth_mean"], 3),
            "queue_depth_max": round(g["queue_depth_max"], 3),
            "phi_spread": round(g["phi_max"] - g["phi_min"], 3),
            "completion_rate": round(g["completed"]
                                     / max(g["generated"], 1.0), 4),
            "sim_t": round(g["t"], 3)}


def _run_streaming(key, cfg: SwarmConfig, strategy, n: int, num_runs: int,
                   chunk_size: int, store: Optional[ResultStore] = None,
                   digest: Optional[str] = None,
                   max_chunks: Optional[int] = None,
                   spans: Optional[Dict] = None,
                   progress=None, label: Optional[str] = None
                   ) -> Dict[str, np.ndarray]:
    chunk = _chunk(chunk_size, num_runs)
    n_chunks = (num_runs + chunk - 1) // chunk
    keys = jax.random.split(key, num_runs)
    strategy = jnp.asarray(strategy, jnp.int32)
    compiled, compile_s = _executable(cfg, n, num_runs, "streaming",
                                      chunk_size)
    if spans is not None:
        spans["_compile_s"] = compile_s
        spans.setdefault("_execute_s", 0.0)

    done, accum = 0, None
    if store is not None and digest is not None:
        done, accum = store.load_partial(digest, chunk_size=chunk)
        done = min(done, n_chunks)

    for c in range(done, n_chunks):
        if max_chunks is not None and c >= max_chunks:
            raise SweepInterrupted(
                f"stopped after {c}/{n_chunks} chunks (max_chunks)")
        ks = _pad_keys(keys[c * chunk:(c + 1) * chunk], chunk)
        t0 = time.perf_counter()
        out = compiled(ks, strategy)
        out = {k: np.asarray(v) for k, v in out.items()}
        if spans is not None:
            spans["_execute_s"] += time.perf_counter() - t0
        if accum is None:
            accum = out
        else:
            accum = {k: np.concatenate([accum[k], out[k]]) for k in accum}
        if store is not None and digest is not None:
            store.save_partial(digest, c + 1, accum, chunk)
        if progress is not None:
            # live swarm health per completed chunk: the flight recorder's
            # final system gauges, when the state stream is on
            row = {"event": "chunk", "label": label, "chunk": c + 1,
                   "chunks": n_chunks, "t": time.time()}
            if "trace_state_sys" in out:
                row.update(_sys_gauges(out["trace_state_sys"]))
            progress.emit(**row)

    return {k: v[:num_runs] for k, v in accum.items()}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def run_batch(key, cfg: SwarmConfig, strategy, n: int, num_runs: int, *,
              backend: str = "vmap", chunk_size: int = DEFAULT_CHUNK,
              spans: Optional[Dict] = None):
    """Run ``num_runs`` Monte-Carlo simulations of ``(cfg, strategy, n)``.

    Returns a dict of ``[num_runs]`` metric arrays (see ``summarize``),
    bit-identical across backends.  ``swarm.run_many`` is a thin wrapper
    over the ``vmap`` backend of this function.  Passing a ``spans`` dict
    fills ``"_compile_s"`` / ``"_execute_s"`` wall-clock spans (the
    execute span blocks on the result).

    On a profiler trace the call is a ``run_batch`` step span: argument
    preparation, any compile, dispatch, and the block where one happens
    (with ``spans``, and always on the sharded and streaming backends).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    with jax.profiler.StepTraceAnnotation(scopes.RUN_BATCH,
                                          step_num=next(_EXECUTIONS)):
        if backend == "vmap":
            compiled, compile_s = _profiled_vmap(cfg, n, num_runs)
            t0 = time.perf_counter()
            out = compiled(key, jnp.asarray(strategy, jnp.int32))
            if spans is not None:
                _block(out)
                spans["_compile_s"] = compile_s
                spans["_execute_s"] = time.perf_counter() - t0
            return out
        if backend == "sharded":
            return _run_sharded(key, cfg, strategy, n, num_runs,
                                spans=spans)
        return {k: jnp.asarray(v) for k, v in _run_streaming(
            key, cfg, strategy, n, num_runs, chunk_size,
            spans=spans).items()}


def run_point(point: SweepPoint, *, backend: str = "vmap",
              store: Optional[ResultStore] = None,
              chunk_size: int = DEFAULT_CHUNK,
              max_chunks: Optional[int] = None,
              progress=None,
              spans: Optional[Dict] = None) -> Dict[str, np.ndarray]:
    """Execute one sweep point, consulting/filling ``store`` if given.

    A caller-supplied ``spans`` dict receives ``"_compile_s"`` /
    ``"_execute_s"`` wall-clock spans when the point is actually computed
    (a store hit fills nothing — it cost neither), keeping the returned
    metrics identical between computed and cached paths.  ``progress``
    additionally receives per-chunk rows (streaming) and a per-point
    ``gauges`` row when the state stream is on.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    digest = point_digest(point) if store is not None else None
    if store is not None:
        hit = store.get(digest)
        if hit is not None:
            return hit
    key = jax.random.PRNGKey(point.seed)
    if backend == "streaming":
        metrics = _run_streaming(key, point.cfg, jnp.int32(point.strategy),
                                 point.n, point.num_runs, chunk_size,
                                 store=store, digest=digest,
                                 max_chunks=max_chunks, spans=spans,
                                 progress=progress, label=point.label)
    else:
        out = run_batch(key, point.cfg, jnp.int32(point.strategy), point.n,
                        point.num_runs, backend=backend, spans=spans)
        metrics = {k: np.asarray(v) for k, v in out.items()}
    if store is not None:
        store.put(digest, metrics, meta={
            "label": point.label, "backend": backend,
            "code_version": code_version()})
    if progress is not None and "trace_state_sys" in metrics:
        progress.emit(event="gauges", label=point.label, t=time.time(),
                      **_sys_gauges(metrics["trace_state_sys"]))
    return metrics


def execute(spec: SweepSpec, *, backend: str = "vmap",
            store: Optional[ResultStore] = None,
            chunk_size: int = DEFAULT_CHUNK,
            verbose: bool = False,
            progress=None) -> Dict[str, Dict[str, np.ndarray]]:
    """Expand and run a whole sweep; returns ``{point.label: metrics}``.

    Each point's wall time (including any cache hit) is recorded under the
    ``"_wall_s"`` pseudo-metric, matching the historical ``timed_sweep``
    convention the benchmark CSVs rely on.  ``progress`` is an optional
    ``ProgressWriter`` (``fleet/dispatch.py``): the single-process path
    then emits the same ``progress.jsonl`` rows as a dispatched run, so
    ``benchmarks/run.py --watch`` works either way.
    """
    points = spec.expand()
    if progress is not None:
        progress.emit(event="sweep_start", sweep=spec.name,
                      total=len(points), t=time.time())
    out = {}
    for pt in points:
        t0 = time.perf_counter()
        spans: Dict[str, float] = {}
        m = dict(run_point(pt, backend=backend, store=store,
                           chunk_size=chunk_size, progress=progress,
                           spans=spans))
        m["_wall_s"] = time.perf_counter() - t0
        # computed points carry the AOT compile/execute split (a store hit
        # fills neither); reports skip underscore keys, so these are purely
        # for the progress surface
        m["_compile_s"] = spans.get("_compile_s")
        m["_execute_s"] = spans.get("_execute_s")
        if verbose:
            print(f"[fleet:{spec.name}] {pt.label} "
                  f"({m['_wall_s']:.2f}s, backend={backend})")
        if progress is not None:
            row = {"event": "point", "label": pt.label,
                   "digest": point_digest(pt) if store is not None
                   else None,
                   "worker": "local", "num_runs": pt.num_runs,
                   "wall_s": round(m["_wall_s"], 3),
                   "cached": spans.get("_execute_s") is None,
                   "t": time.time()}
            if m["_compile_s"] is not None:
                row["compile_s"] = round(m["_compile_s"], 3)
                row["execute_s"] = round(m["_execute_s"], 3)
            progress.emit(**row)
        out[pt.label] = m
    return out

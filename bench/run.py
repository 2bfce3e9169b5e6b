"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics come from
``BENCHMARK.json`` at the root of the checkout and the files under ``bench/``
that they name (``bench/spec.py``).  The run:

1. looks for the chips first: without a TPU, or with fewer devices than the
   cell asks for, it exits with code 2 and prints no result;
2. keeps JAX's persistent compilation cache at ``<checkout>/.jax_cache``;
3. hands the cell to its runner (``bench/runners/<kind>.py``), which sets
   up, measures ``--seconds``, reads the device memory peak and compares
   what the timed path produced with the plain reference;
4. with ``--trace 1``, reduces the profiler trace of the window's first
   executions to the cell's per-layer metrics (``bench/metrics/*.py``);
5. prints set-up accounting on earlier lines, each compared number beside
   its limit as the last lines of standard error, and one JSON object as the
   last line of standard output: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
   ``checks``.

With ``--trace 0`` the metrics are the cell's end-to-end ones, with
``--trace 1`` its per-layer ones.  ``setup_s`` runs from the start of this
process to the start of the window.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileEvents:
    """Counts JAX's compile requests and persistent-cache hits and misses
    from its monitoring events."""

    NAMES = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
             "/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.counts = {"compiles": 0, "compile_s": 0.0,
                       **{v: 0 for v in self.NAMES.values()}}

    def install(self) -> None:
        import jax.monitoring as mon
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def uninstall(self) -> None:
        import jax.monitoring as mon
        mon.unregister_event_listener(self._event)
        mon.unregister_event_duration_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event in self.NAMES:
            self.counts[self.NAMES[event]] += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == self.COMPILE:
            self.counts["compiles"] += 1
            self.counts["compile_s"] += duration

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}


def check_devices(chips: int):
    """The devices, or an error message when they are not enough TPU
    chips."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"no TPU: JAX found {devices[0].platform}"
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
    return devices, None


def per_layer(cell, trace_path: str, counters: dict, kind: str):
    """The cell's per-layer metrics, the device summary and the breakdown,
    from the trace of the window's first executions."""
    from bench import trace as tr
    from bench import work
    t = tr.load(trace_path)
    counters = dict(counters, peaks=work.peaks(kind))
    metrics = {}
    for m in cell.per_layer():
        value = cell.reader(m["name"]).read(t, counters)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    busy = [t.busy_s(d) for d in t.devices]
    device = {"busy_s": sum(busy) / len(busy), "window_s": t.window_s()}
    return metrics, device, t.breakdown()


def use_cache(cache_dir: str) -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile, and with no
    size bound: a bound makes JAX keep an access-time file beside each
    entry, and one entry without its file (a write cut short) then makes
    every later write fail."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    from repro.chip import enable_compile_cache
    enable_compile_cache()


def main(argv=None, check=check_devices, root: str = ROOT,
         cache: bool = True) -> int:
    """One run of one cell.  ``check`` finds the chips (tests replace it to
    drive a run on the CPU); ``root`` is the checkout that holds
    ``BENCHMARK.json`` and ``bench/``; ``cache`` turns on the persistent
    compilation cache."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import Cell, load_benchmark
    cell = Cell(args.workload, load_benchmark(root),
                os.path.join(root, "bench"))
    cache_dir = os.path.join(root, ".jax_cache")
    trace_dir = os.path.join(root, ".bench_trace")

    t0 = time.perf_counter()
    devices, err = check(cell.chips)
    if err:
        print(f"bench/run.py: {err}", file=sys.stderr)
        return 2
    backend_init_s = time.perf_counter() - t0
    if cache:
        use_cache(cache_dir)
    events = CompileEvents()
    events.install()
    window = {}
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
           "trace": bool(args.trace), "trace_dir": trace_dir,
           "events": events, "log": log,
           "window_started": lambda t: window.setdefault("t", t)}
    try:
        res = cell.runner().run(ctx)
    finally:
        events.uninstall()
    setup_s = window["t"] - T_PROCESS

    setup = {"backend_init_s": backend_init_s, "setup_s": setup_s,
             "compile_cache_dir": cache_dir if cache else None,
             **res["info"], "compile_events_total": events.snapshot()}
    log("setup " + json.dumps(setup))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}
    if args.trace:
        paths = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise RuntimeError(f"expected one trace file, found {paths}")
        try:
            metrics, busy, breakdown = per_layer(
                cell, paths[0], res["counters"], devices[0].device_kind)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy)
        line.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = dict(res["metrics"], setup_s=setup_s)
        line.update(metrics={m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end()},
                    device=device)
    line["checks"] = res["checks"]
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

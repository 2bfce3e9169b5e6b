"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/tests/record_trace.py      # on a machine with one TPU

Runs the swarm runner's traced window on a tiny cut of ``swarm-paper``
(2 runs, two 200 ms epochs of 20 ticks per execution, two traced
executions) and writes the trace, gzipped, with the runner's counters to
``bench/tests/data/``.
"""
from __future__ import annotations

import gzip
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

# two epochs: with one, the decision at t = 0 sees empty queues, and XLA
# folds it and the φ update away
TINY = {"num_runs": 2, "sim_time_s": 0.4}


def main() -> int:
    from bench import run, spec
    tmp = tempfile.mkdtemp()
    try:
        shutil.copytree(spec.BENCH, os.path.join(tmp, "bench"))
        doc = spec.load_benchmark()
        conf = json.load(open(os.path.join(tmp, "bench", "configs",
                                           "swarm-paper.json")))
        conf["swarm"].update(TINY)
        conf["trace"] = {"executions": 2}
        conf["compare"]["block"] = TINY["num_runs"]
        json.dump(conf, open(os.path.join(tmp, "bench", "configs",
                                          "swarm-tiny.json"), "w"))
        doc["configs"].append(dict(doc["configs"][0], name="swarm-tiny",
                                   file="bench/configs/swarm-tiny.json"))
        doc["workloads"].append({"name": "tiny", "config": "swarm-tiny",
                                 "traffic": "table2-dist", "chips": 1,
                                 "why": "trace fixture"})
        json.dump(doc, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
        devices, err = run.check_devices(1)
        if err:
            print(err, file=sys.stderr)
            return 2
        cell = spec.Cell("tiny", doc, os.path.join(tmp, "bench"))
        events = run.CompileEvents()
        trace_dir = os.path.join(tmp, "trace")
        res = cell.runner().run({
            "cell": cell, "seed": 1, "seconds": 0.1, "trace": True,
            "trace_dir": trace_dir, "events": events, "log": print,
            "window_started": lambda t: None})
        path = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb")][0]
        os.makedirs(os.path.join(HERE, "data"), exist_ok=True)
        out = os.path.join(HERE, "data", "swarm_tiny.xplane.pb.gz")
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            dst.write(src.read())
        counters = dict(res["counters"], device_kind=devices[0].device_kind)
        json.dump(counters, open(os.path.join(HERE, "data",
                                              "swarm_tiny.counters.json"),
                                 "w"), indent=1)
        print(f"wrote {out} ({os.path.getsize(out)} bytes), correct "
              f"{res['correct']}")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

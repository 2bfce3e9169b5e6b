"""`repro.trace` — per-task and per-hop telemetry (DESIGN.md §10).

The simulator only accumulates scalar sums; this package captures one
fixed-width :mod:`~repro.trace.schema` TaskRecord per completed (and
dropped) task — and, as a second stream, one HopRecord per delivered
transfer — *inside* the jitted scan (:mod:`~repro.trace.record` — no
host callbacks, vmap/shard_map/lax.map-safe), decodes the buffers on the
host (:mod:`~repro.trace.decode`), aggregates them into the paper's
task- and hop-level indices — latency CDF, Jain fairness over task
latencies, hop and exit histograms, energy per task, per-hop transfer
time and per-link bits with the queue-wait vs in-flight decomposition
(:mod:`~repro.trace.aggregate`) — and exports a Chrome-trace/Perfetto
timeline with true per-hop slices and flow arrows
(:mod:`~repro.trace.export`).

A third stream, the epoch-indexed swarm-state **flight recorder**
(``SwarmConfig.trace_state_every > 0``; DESIGN.md §12), snapshots
per-node gauges (φ, queue depth, cumulative energy, alive, in-flight
bits) plus system aggregates every N-th epoch; ``decode_state`` /
``state_indices`` turn it into φ-convergence curves, queue-depth
heatmaps, energy-drain trajectories and imbalance indices, and
``state_counter_events`` renders Perfetto counter tracks.

:mod:`~repro.trace.critical` decomposes each traced task's end-to-end
latency into compute / queue-wait / airtime / fault-stall segments that
sum back exactly (DESIGN.md §14.4) — ``segment_indices`` feeds the BENCH
``latency_segments`` payload and ``attribute`` names the segment that
moved between two of them.

Enabled by ``SwarmConfig.trace_capacity > 0`` (tasks),
``SwarmConfig.trace_hop_capacity > 0`` (hops) and
``SwarmConfig.trace_state_every > 0`` (state), independently; with the
defaults 0 no trace state exists anywhere and the simulator is
bit-identical to an untraced build.
"""
from repro.trace import schema
from repro.trace.aggregate import (exit_label_histogram, hop_airtime_s,
                                   hop_energy_j, hop_histogram, hop_indices,
                                   int_histogram, jain_fairness, link_bits,
                                   link_energy_j, quantile_summary,
                                   state_indices, trace_indices)
from repro.trace.critical import (SEGMENTS, attribute, decompose,
                                  hop_stall_fraction, segment_indices)
from repro.trace.decode import decode, decode_hops, decode_state, split_runs
from repro.trace.export import (chrome_trace_events, hop_trace_events,
                                state_counter_events, write_chrome_trace)
from repro.trace.record import (init_hops, init_state_stream, init_trace,
                                state_enabled, traced_push,
                                write_hop_records, write_records,
                                write_state)

__all__ = ["schema", "decode", "decode_hops", "decode_state", "split_runs",
           "trace_indices", "hop_indices", "state_indices", "link_bits",
           "hop_airtime_s", "hop_energy_j", "link_energy_j",
           "quantile_summary", "jain_fairness",
           "hop_histogram", "exit_label_histogram", "int_histogram",
           "chrome_trace_events", "hop_trace_events",
           "state_counter_events", "write_chrome_trace",
           "init_trace", "init_hops", "init_state_stream", "state_enabled",
           "traced_push", "write_records", "write_hop_records",
           "write_state",
           "SEGMENTS", "decompose", "segment_indices", "attribute",
           "hop_stall_fraction"]

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell and
extract roofline terms from the compiled artifacts.  (Deliverables e/g.)

Two passes per cell:
  * census  — the production step (scan-over-layers, full depth)
              lowered + compiled; proves sharding coherence and yields
              ``memory_analysis()`` (the real per-device footprint).
  * costing — XLA's HLO cost analysis counts a while-loop body once, so
              FLOP/byte/collective numbers come from *unrolled* compiles at
              two reduced depths (full width/batch/seq), linearly
              extrapolated to full depth: cost(d) = a + b·d.  Inner
              q-chunk/ssm-chunk loops are unrolled too (exact accounting).
              Single-pod only (the roofline table's mesh).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
        --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Writes one JSON per cell under benchmarks/artifacts/dryrun/<mesh>/.
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices; jax
# locks the device count on first init, so this precedes every other import.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import re                # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.chip import enable_compile_cache                          # noqa: E402
from repro.configs import (ARCHS, SHAPES, get_config, get_shape,             # noqa: E402
                           shape_applicable)
from repro.launch.mesh import make_production_mesh                           # noqa: E402
from repro.launch.step import cell_structs                                   # noqa: E402

# --- TPU v5e hardware model (per brief) ------------------------------------
PEAK_FLOPS = 197e12         # bf16 FLOP/s per chip
HBM_BW = 819e9              # B/s per chip
LINK_BW = 50e9              # B/s per ICI link

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                            os.pardir, "benchmarks", "artifacts", "dryrun")

_SHAPE_RE = re.compile(r"(f32|f16|bf16|f64|s32|s8|u32|u8|s64|pred|u64|s16|u16)"
                       r"\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+(\(?[^)=]*?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
                "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                "s8": 1, "u8": 1, "pred": 1}

# per-device link-traffic factor ≈ factor × output_bytes (ring algorithms);
# reduce-scatter additionally scales by the group size (input = n × output).
_TRAFFIC_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                   "reduce-scatter": 1.0, "all-to-all": 1.0,
                   "collective-permute": 1.0}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dtp, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtp]
    return total


def collective_bytes(hlo_text: str):
    """Per-device collective link-traffic estimate + op census from the
    post-SPMD HLO (output shapes × ring factors)."""
    per_op = {}
    count = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:   # async pair: count the -start only
            continue
        out_bytes = _shape_bytes(m.group(1))
        op = m.group(2)
        factor = _TRAFFIC_FACTOR[op]
        if op == "reduce-scatter":
            g = _GROUPS_RE.search(line)
            if g:
                factor = max(int(g.group(2)) - 1, 1)
        per_op[op] = per_op.get(op, 0.0) + factor * out_bytes
        count[op] = count.get(op, 0) + 1
    return per_op, count


def model_flops(cfg, shape) -> float:
    """6·N_active·tokens (train) / 2·N_active·tokens (inference)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch       # decode: 1 token/seq


def _compile_cell(cfg, shape, mesh):
    """lower + compile one step; returns (compiled, t_lower, t_compile)."""
    t0 = time.time()
    with mesh:
        fn, structs, out_sh, _ = cell_structs(cfg, shape, mesh)
        donate = (0,) if shape.kind == "train" else (
            (1,) if shape.kind == "decode" else ())
        jitted = jax.jit(fn, out_shardings=out_sh, donate_argnums=donate)
        lowered = jitted.lower(*structs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return compiled, t_lower, t_compile


def _depth_plan(cfg):
    """(d1, d2, units_full) for the cost extrapolation; depths in layers,
    units in extrapolation steps (superblocks for hybrid — the 38-layer
    config's 2-layer tail is covered by the fractional 38/3 unit count)."""
    if cfg.family == "hybrid":
        n = len(cfg.hybrid.pattern)
        return n, 2 * n, cfg.num_layers / n
    # encdec scales encoder and decoder depth together (24/24 config)
    return 2, 4, float(cfg.num_layers)


def _at_depth(cfg, depth, shape):
    """Depth-reduced unrolled config for costing.  Inner chunk loops are
    unrolled too (exact accounting), so their chunk sizes are raised to
    bound the unroll factor at <=16 iterations — totals are unchanged
    (the chunked ops are linear in S)."""
    kw = {"num_layers": depth, "scan_layers": False,
          "attn_chunk": max(cfg.attn_chunk, shape.seq_len // 16)}
    if cfg.family == "encdec":
        kw["encdec"] = dataclasses.replace(cfg.encdec, encoder_layers=depth)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, chunk=max(cfg.ssm.chunk, shape.seq_len // 16))
    if cfg.loss_chunk:
        kw["loss_chunk"] = max(cfg.loss_chunk, shape.seq_len // 16)
    return dataclasses.replace(cfg, **kw)


def _cost_once(cfg, shape, mesh):
    compiled, _, _ = _compile_cell(cfg, shape, mesh)
    ca = compiled.cost_analysis() or {}
    coll, coll_n = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": coll, "coll_n": coll_n}


def _extrapolate(c1, c2, d1, d2, units_full, unit):
    """cost(d) = a + b·d (d in layers), report at units_full·unit layers."""
    def lin(v1, v2):
        b = (v2 - v1) / (d2 - d1)
        a = v1 - b * d1
        return a + b * units_full * unit

    out = {"flops": lin(c1["flops"], c2["flops"]),
           "bytes": lin(c1["bytes"], c2["bytes"])}
    ops = set(c1["coll"]) | set(c2["coll"])
    out["coll"] = {op: max(lin(c1["coll"].get(op, 0.0),
                               c2["coll"].get(op, 0.0)), 0.0) for op in ops}
    out["coll_n"] = {op: int(round(max(
        lin(c1["coll_n"].get(op, 0), c2["coll_n"].get(op, 0)), 0)))
        for op in set(c1["coll_n"]) | set(c2["coll_n"])}
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, cfg_override=None, tag: str = ""):
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"{arch}__{shape_name}{tag}.json")
    if os.path.exists(out_path) and not force:
        print(f"[skip-cached] {arch} × {shape_name} × {mesh_kind}")
        return json.load(open(out_path))

    cfg = cfg_override or get_config(arch)
    shape = get_shape(shape_name)
    ok, reason = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "kind": shape.kind}
    if not ok:
        rec.update({"status": "SKIP", "reason": reason})
        json.dump(rec, open(out_path, "w"), indent=1)
        print(f"[SKIP] {arch} × {shape_name}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = mesh.devices.size
    try:
        # ---- census: production (scanned) step, full depth --------------
        compiled, t_lower, t_compile = _compile_cell(
            dataclasses.replace(cfg, scan_layers=True), shape, mesh)
        ma = compiled.memory_analysis()
        mem = {}
        if ma is not None:
            mem = {"argument_bytes": ma.argument_size_in_bytes,
                   "output_bytes": ma.output_size_in_bytes,
                   "temp_bytes": ma.temp_size_in_bytes,
                   "alias_bytes": ma.alias_size_in_bytes,
                   "peak_estimate_bytes": (ma.argument_size_in_bytes
                                           + ma.output_size_in_bytes
                                           + ma.temp_size_in_bytes
                                           - ma.alias_size_in_bytes)}
        rec.update({"status": "OK", "chips": chips,
                    "lower_s": round(t_lower, 2),
                    "compile_s": round(t_compile, 2), "memory": mem})
        del compiled

        # ---- costing: depth-extrapolated unrolled compiles --------------
        if mesh_kind == "single":
            d1, d2, units_full = _depth_plan(cfg)
            c1 = _cost_once(_at_depth(cfg, d1, shape), shape, mesh)
            c2 = _cost_once(_at_depth(cfg, d2, shape), shape, mesh)
            full = _extrapolate(c1, c2, d1, d2,
                                units_full, cfg.num_layers / units_full)
            flops_dev, bytes_dev = full["flops"], full["bytes"]
            coll_dev = float(sum(full["coll"].values()))
            mf = model_flops(cfg, shape)
            t_compute = flops_dev / PEAK_FLOPS
            t_memory = bytes_dev / HBM_BW
            t_coll = coll_dev / LINK_BW
            dominant = max((("compute", t_compute), ("memory", t_memory),
                            ("collective", t_coll)),
                           key=lambda kv: kv[1])[0]
            rec.update({
                "flops_per_device": flops_dev,
                "hlo_flops_global": flops_dev * chips,
                "bytes_per_device": bytes_dev,
                "collective_bytes_per_device": coll_dev,
                "collective_by_op": full["coll"],
                "collective_op_counts": full["coll_n"],
                "model_flops": mf,
                "useful_flop_ratio": mf / max(flops_dev * chips, 1.0),
                "roofline": {"compute_s": t_compute, "memory_s": t_memory,
                             "collective_s": t_coll, "dominant": dominant,
                             "bound_step_s": max(t_compute, t_memory,
                                                 t_coll)},
            })
            print(f"[OK] {arch} × {shape_name} × {mesh_kind}: "
                  f"compile={t_compile:.1f}s dom={dominant} "
                  f"comp={t_compute*1e3:.2f}ms mem={t_memory*1e3:.2f}ms "
                  f"coll={t_coll*1e3:.2f}ms "
                  f"useful={rec['useful_flop_ratio']:.2f}", flush=True)
        else:
            print(f"[OK] {arch} × {shape_name} × {mesh_kind}: "
                  f"compile={t_compile:.1f}s (census only)", flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update({"status": "FAIL", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
        print(f"[FAIL] {arch} × {shape_name} × {mesh_kind}: {e}", flush=True)
    json.dump(rec, open(out_path, "w"), indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=os.path.normpath(ARTIFACT_DIR))
    args = ap.parse_args()
    enable_compile_cache()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = sorted(SHAPES) if (args.all or not args.shape) else [args.shape]

    n_fail = 0
    for mk in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mk, os.path.join(args.out, mk),
                               force=args.force)
                n_fail += rec.get("status") == "FAIL"
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()

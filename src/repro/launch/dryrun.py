import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede any other import (jax locks the device
# count at first init).  This module is the multi-pod dry-run driver: it AOT
# lowers + compiles every (architecture x input-shape x mesh) cell with
# ShapeDtypeStruct inputs (no allocation), records memory/cost analyses and
# the collective schedule, and caches per-cell JSON for the roofline report.

import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.shapes import SHAPES, cells, input_specs, shape_applicable
from repro.distributed import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.serve_step import abstract_cache, make_decode_step, make_prefill
from repro.train.train_step import abstract_train_state, make_train_step

# ---------------------------------------------------------------------------
# collective-schedule parsing (HLO text -> per-device bytes on the wire)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-device wire bytes per collective kind (ring-algorithm estimates).

    Sizes in partitioned HLO are already per-device shards.  Ring costs:
      all-reduce     2 (g-1)/g * result
      all-gather       (g-1)/g * result      (result = gathered size)
      reduce-scatter   (g-1)   * result      (result = scattered shard)
      all-to-all       (g-1)/g * result
      collective-permute         result
    """
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        shape_str, op = m.groups()
        size = _shape_bytes(shape_str)
        g_m = _GROUPS_RE.search(line)
        g = len(g_m.group(1).split(",")) if g_m else 2
        g = max(g, 2)
        if op == "all-reduce":
            size = 2 * (g - 1) / g * size
        elif op == "all-gather":
            size = (g - 1) / g * size
        elif op == "reduce-scatter":
            size = (g - 1) * size
        elif op == "all-to-all":
            size = (g - 1) / g * size
        out[op] += size
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def build_cell(arch: str, shape_name: str, mesh, *, microbatches: int = 1,
               cfg_override=None, overrides: dict | None = None,
               kv_shard: str = "heads", zero1: bool = False):
    """-> (jitted_fn, abstract_args) ready to .lower(*args)."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    model = build_model(cfg)
    s = SHAPES[shape_name]
    key = jax.random.PRNGKey(0)
    batch_abs = input_specs(cfg, shape_name)

    if s.kind == "train":
        state_abs = abstract_train_state(model, key)
        # Calibration compiles (unroll_layers=True) unroll the microbatch
        # loop as well, so HloCostAnalysis counts every microbatch.
        step = make_train_step(model, AdamWConfig(), microbatches=microbatches,
                               unroll=cfg.unroll_layers)
        state_sh = jax.tree.map(
            lambda _: None, state_abs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        state_sh = type(state_abs)(
            params=SH.params_shardings(mesh, state_abs.params),
            opt=type(state_abs.opt)(
                count=SH.replicated(mesh),
                m=SH.opt_state_shardings(mesh, state_abs.opt.m,
                                         zero1=zero1),
                v=SH.opt_state_shardings(mesh, state_abs.opt.v,
                                         zero1=zero1)),
            step=SH.replicated(mesh))
        batch_sh = SH.batch_shardings(mesh, batch_abs)
        metrics_sh = jax.tree.map(lambda _: SH.replicated(mesh),
                                  {"loss": 0, "ce": 0, "aux": 0,
                                   "grad_norm": 0, "lr": 0})
        fn = jax.jit(step,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, metrics_sh),
                     donate_argnums=(0,))
        return fn, (state_abs, batch_abs)

    # serving cells
    params_abs = jax.eval_shape(model.init, key)
    params_sh = SH.params_shardings(mesh, params_abs)
    B = s.global_batch

    if s.kind == "prefill":
        cache_abs = abstract_cache(model, B, s.seq_len)
        cache_sh = (SH.cache_shardings(mesh, cache_abs, kv_shard=kv_shard)
                    if cache_abs is not None else None)
        prefill = make_prefill(model)
        tok_sh = SH.batch_shardings(
            mesh, jax.ShapeDtypeStruct((B, 1), jnp.int32))
        batch_sh = SH.batch_shardings(mesh, batch_abs)
        if cfg.family == "audio":
            # encoder-only: prefill = full encode, returns logits
            def enc(params, batch):
                logits, _ = model.prefill(params, batch, None)
                return logits
            out_shape = (B, s.seq_len, cfg.vocab_size)
            fn = jax.jit(enc, in_shardings=(params_sh, batch_sh),
                         out_shardings=SH.logits_sharding(mesh, out_shape))
            return fn, (params_abs, batch_abs)
        fn = jax.jit(prefill,
                     in_shardings=(params_sh, batch_sh, cache_sh),
                     out_shardings=(tok_sh, cache_sh),
                     donate_argnums=(2,))
        return fn, (params_abs, batch_abs, cache_abs)

    assert s.kind == "decode"
    cache_abs = abstract_cache(model, B, s.seq_len)
    cache_sh = SH.cache_shardings(mesh, cache_abs, kv_shard=kv_shard)
    serve = make_decode_step(model)
    tok_abs = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = SH.batch_shardings(mesh, tok_abs)
    fn = jax.jit(serve,
                 in_shardings=(params_sh, tok_sh, cache_sh),
                 out_shardings=(tok_sh, cache_sh),
                 donate_argnums=(2,))
    return fn, (params_abs, tok_abs, cache_abs)


# ---------------------------------------------------------------------------
# scan-trip-count calibration
#
# XLA's HloCostAnalysis counts a while-loop body ONCE (trip counts are not
# statically applied), so the scan-over-layers models under-report FLOPs /
# bytes / collective traffic by ~num_layers.  We recover exact totals with a
# two-point fit: compile the same cell with g=1 and g=2 layer groups
# *unrolled* (identical math, python loop), then
#     X(G) = X(1) + (X(2) - X(1)) * (G - 1).
# Exact for uniform groups (all our scans are).  xlstm has no layer scan
# (layers are a python loop) but scans over TIME; its recurrence cost is
# added analytically below.
# ---------------------------------------------------------------------------

def _calib_plan(cfg):
    """-> (n_layers_for_g, G_full) or None if no layer scan to calibrate."""
    if cfg.family == "ssm":
        return None
    if cfg.family == "hybrid":
        return (lambda g: g * cfg.attn_every), cfg.num_layers // cfg.attn_every
    group = 2 if cfg.local_global_pattern else 1
    prefix = cfg.first_dense_layers
    G_full = (cfg.num_layers - prefix) // group
    return (lambda g: prefix + g * group), G_full


def _xlstm_time_correction(cfg, shape):
    """Analytic per-step recurrence cost x (S-1) missed by the time scan."""
    s = SHAPES[shape]
    if s.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    B_local = s.global_batch            # cost_analysis is per-device; batch
    # is sharded over data axes — caller divides by dp size.
    S = s.seq_len
    H = cfg.num_heads
    d = cfg.d_model
    every = cfg.slstm_every or (cfg.num_layers + 1)
    n_slstm = sum(1 for i in range(cfg.num_layers) if (i % every) == every - 1)
    n_mlstm = cfg.num_layers - n_slstm
    Dh_m = (2 * d) // H
    Dh_s = d // H
    f_m = 5.0 * B_local * H * Dh_m ** 2 + 10.0 * B_local * H * Dh_m
    f_s = 8.0 * B_local * H * Dh_s ** 2 + 24.0 * B_local * H * Dh_s
    flops = (S - 1) * (n_mlstm * f_m + n_slstm * f_s)
    bytes_ = (S - 1) * 2 * 4 * (n_mlstm * B_local * H * (Dh_m ** 2 + 2 * Dh_m + 1)
                                + n_slstm * 4 * B_local * H * Dh_s)
    return {"flops": float(flops), "bytes": float(bytes_)}


def _cost_of(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def calibrate(arch: str, shape_name: str, mesh, *, microbatches: int = 1,
              overrides: dict | None = None, kv_shard: str = "heads"):
    """-> dict with corrected per-device cost + collectives (or None)."""
    cfg = get_config(arch)
    plan = _calib_plan(cfg)
    if plan is None:
        return None
    n_of_g, G_full = plan
    points = {}
    for g in (1, 2):
        cfg_g = dataclasses.replace(cfg, num_layers=n_of_g(g),
                                    unroll_layers=True, **(overrides or {}))
        fn, args = build_cell(arch, shape_name, mesh,
                              microbatches=microbatches, cfg_override=cfg_g,
                              kv_shard=kv_shard)
        compiled = fn.lower(*args).compile()
        points[g] = {"cost": _cost_of(compiled),
                     "collectives": collective_stats(compiled.as_text())}

    def fit(x1, x2):
        return x1 + (x2 - x1) * (G_full - 1)

    c1, c2 = points[1]["cost"], points[2]["cost"]
    col1 = points[1]["collectives"], points[2]["collectives"]
    col1, col2 = col1[0], col1[1]
    corrected = {
        "flops": fit(c1["flops"], c2["flops"]),
        "bytes_accessed": fit(c1["bytes_accessed"], c2["bytes_accessed"]),
        "collective_bytes": fit(col1["total_bytes"], col2["total_bytes"]),
        "collective_bytes_by_op": {
            k: fit(col1["bytes"][k], col2["bytes"][k])
            for k in col1["bytes"]},
        "G_full": G_full,
        "points": points,
    }
    return corrected


# ---------------------------------------------------------------------------
# run + record
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path, force: bool = False,
             microbatches: int = 1, tag: str = "",
             overrides: dict | None = None, kv_shard: str = "heads",
             zero1: bool = False) -> dict:
    mesh_name = "multi" if multi_pod else "single"
    suffix = f"-{tag}" if tag else ""
    out_path = out_dir / f"{arch}--{shape_name}--{mesh_name}{suffix}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())

    cfg = get_config(arch)
    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "skipped": True, "reason": reason}
        out_path.write_text(json.dumps(rec, indent=2))
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "devices": int(mesh.devices.size), "skipped": False,
           "microbatches": microbatches, "tag": tag,
           "overrides": overrides or {}, "kv_shard": kv_shard}
    try:
        with jax.set_mesh(mesh):
            fn, args = build_cell(arch, shape_name, mesh,
                                  microbatches=microbatches,
                                  overrides=overrides, kv_shard=kv_shard,
                                  zero1=zero1)
            lowered = fn.lower(*args)
            t_lower = time.time()
            compiled = lowered.compile()
            t_compile = time.time()

            mem = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
                "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
                "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            }
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            rec["cost"] = {
                "flops": float(cost.get("flops", -1)),
                "bytes_accessed": float(cost.get("bytes accessed", -1)),
                "transcendentals": float(cost.get("transcendentals", -1)),
            }
            hlo = compiled.as_text()
            rec["collectives"] = collective_stats(hlo)
            rec["timings"] = {"lower_s": t_lower - t0,
                              "compile_s": t_compile - t_lower}

            # Roofline-grade corrected costs (single-pod mesh only).
            if not multi_pod:
                corr = calibrate(arch, shape_name, mesh,
                                 microbatches=microbatches,
                                 overrides=overrides, kv_shard=kv_shard)
                if corr is None:                      # xlstm: layers unrolled
                    tc = _xlstm_time_correction(cfg, shape_name)
                    dp = mesh.devices.shape[0]        # batch shard factor
                    corr = {
                        "flops": rec["cost"]["flops"] + tc["flops"] / dp,
                        "bytes_accessed": (rec["cost"]["bytes_accessed"]
                                           + tc["bytes"] / dp),
                        "collective_bytes":
                            rec["collectives"]["total_bytes"],
                        "collective_bytes_by_op":
                            rec["collectives"]["bytes"],
                        "G_full": 1,
                        "note": "layers unrolled natively; analytic time-scan"
                                " correction added",
                    }
                rec["cost_corrected"] = {
                    k: corr[k] for k in
                    ("flops", "bytes_accessed", "collective_bytes",
                     "collective_bytes_by_op", "G_full")}
                rec["calib_note"] = corr.get("note", "2-point unrolled fit")
            rec["ok"] = True
    except Exception as e:  # record failures — they are dry-run bugs
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, help="one arch (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="", help="variant tag for perf iterations")
    ap.add_argument("--kv-shard", default="heads", choices=["heads", "seq", "auto"])
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (repeatable)")
    args = ap.parse_args()

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"true": True, "false": False}.get(
            v.lower(), int(v) if v.lstrip("-").isdigit() else v)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    grid = cells(args.arch)
    if args.shape:
        grid = [(a, s) for a, s in grid if s == args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    for arch, shape_name in grid:
        for multi_pod in meshes:
            t0 = time.time()
            rec = run_cell(arch, shape_name, multi_pod=multi_pod,
                           out_dir=out_dir, force=args.force,
                           microbatches=args.microbatches, tag=args.tag,
                           overrides=overrides, kv_shard=args.kv_shard,
                           zero1=args.zero1)
            status = ("SKIP " + rec.get("reason", "") if rec.get("skipped")
                      else "OK" if rec.get("ok") else
                      "FAIL " + rec.get("error", "")[:120])
            mesh_name = "multi" if multi_pod else "single"
            print(f"[{time.strftime('%H:%M:%S')}] {arch:22s} {shape_name:12s} "
                  f"{mesh_name:6s} {time.time()-t0:7.1f}s  {status}", flush=True)


if __name__ == "__main__":
    main()

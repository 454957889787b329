"""Multi-pod dry-run: lower + compile every (arch × shape × mesh).

This is the proof that the distribution config is coherent without real
hardware: 512 placeholder host devices build the production meshes, every
step function must lower AND compile against them, and the compiled
artifact yields the roofline terms (cost_analysis + collective bytes from
the HLO) recorded in EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b \
      --shape train_4k [--multi-pod]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
      --out experiments/dryrun.jsonl
"""
import argparse
import json
import os
import re
import sys
import time
from typing import Any, Dict, Optional

import jax

from repro.configs import (ASSIGNED_ARCHS, INPUT_SHAPES, get_config,
                           long_context_variant, serving_variant)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_step
from repro.sharding import sharding_ctx

# --- TPU v5e hardware constants (roofline denominators) -------------------
PEAK_FLOPS = 197e12          # bf16 per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[16,1024]{1,0}'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op, by op kind.

    Output-shape bytes is the standard proxy for wire traffic (exact
    per-algorithm factors like the all-gather's (n-1)/n are dropped; they
    are ≤1 and uniform across the comparisons we make).
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # '%x = TYPE[...] all-gather(...)' — op name after the shape
        m = re.search(r"=\s+((?:\([^)]*\)|\S+))\s+([\w-]+)", ls)
        if not m:
            continue
        op = m.group(2).rstrip(".0123456789")
        if op.endswith("-start"):
            op = op[:-6]
        if op in _COLLECTIVES:
            out[op] += _shape_bytes(m.group(1))
    return out


def _computations(hlo_text: str) -> Dict[str, str]:
    """Split an HLO module's text into named computation bodies."""
    comps: Dict[str, list] = {}
    cur = None
    for line in hlo_text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(", line)
        if m and line.rstrip().endswith("{"):
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                comps["__entry__"] = comps[cur]
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return {k: "\n".join(v) for k, v in comps.items()}


_WHILE_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'known_trip_count":\{"n":"(\d+)"')


def collective_bytes_scaled(hlo_text: str) -> Dict[str, int]:
    """Collective bytes with while-loop bodies ×known_trip_count.

    ``lax.scan`` lowers to a while loop whose body appears ONCE in the
    module; XLA records the trip count in the op's backend_config.  We
    recurse through nested loops so per-layer collectives are counted
    once per layer, not once per program.
    """
    comps = _computations(hlo_text)
    memo: Dict[str, Dict[str, int]] = {}

    def total(name: str) -> Dict[str, int]:
        if name in memo:
            return memo[name]
        memo[name] = {k: 0 for k in _COLLECTIVES}   # break cycles
        text = comps.get(name, "")
        out = collective_bytes(text)
        for line in text.splitlines():
            if " while(" not in line:
                continue
            mb = _WHILE_BODY_RE.search(line)
            mt = _TRIP_RE.search(line)
            trip = int(mt.group(1)) if mt else 1
            if mb and mb.group(1) in comps:
                sub = total(mb.group(1))
                for k, v in sub.items():
                    out[k] += trip * v
        memo[name] = out
        return out

    return total("__entry__")


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # one token per sequence


def baseline_variant(cfg):
    """Paper-faithful pre-optimisation parallelism (the §Perf baseline):
    the naive sharding a straightforward port would use — seq-parallel
    hints on, pjit-only MoE dispatch, replicated decode cache, FSDP
    everywhere.  Selected with --baseline / baseline=True."""
    import dataclasses
    kw = dict(seq_parallel=True, context_parallel_decode=False)
    cfg = cfg.with_(parallel=dataclasses.replace(cfg.parallel, **kw))
    if cfg.moe is not None and cfg.moe.dispatch == "a2a":
        disp = "sort_scatter" if cfg.moe.n_experts > 64 else "dense_onehot"
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, dispatch=disp))
    return cfg


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               mesh=None, verbose: bool = True,
               baseline: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    t0 = time.time()
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "chips": n_chips, "variant": "baseline" if baseline else "optimized",
    }
    eff_cfg = long_context_variant(cfg) if shape.name == "long_500k" else cfg
    if baseline:
        eff_cfg = baseline_variant(eff_cfg)
        cfg = eff_cfg
    elif shape.kind == "decode":
        eff_cfg = serving_variant(eff_cfg)       # §Perf G4: no FSDP at decode
    elif shape.kind == "train":
        from repro.launch.steps import train_variant
        eff_cfg = train_variant(eff_cfg)         # §Perf Q1
    rec["attn_window"] = eff_cfg.attn_window
    from repro.launch.roofline import scan_corrections
    with sharding_ctx(mesh, eff_cfg) as ctx:
        fn, args, in_sh = make_step(cfg, shape, ctx,
                                    serving_fsdp_off=not baseline)
        # decode donates its cache (as a serving loop does every step);
        # train donates params+opt. Without donation XLA materialises a
        # full temp copy of the donated buffers (§Perf G3).
        donate = () if baseline else \
            {"decode": (1,), "train": (0, 1)}.get(shape.kind, ())
        lowered = jax.jit(fn, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        # collectives: exact — while bodies scaled by known_trip_count
        coll = collective_bytes_scaled(compiled.as_text())
        # flops: cost_analysis counts scan bodies once; correct by lowering
        # each stage body separately (launch/roofline.py)
        extra, per_stage = scan_corrections(eff_cfg, shape, ctx,
                                            collective_bytes)

    flops_raw = float(cost.get("flops", 0.0))
    bytes_raw = float(cost.get("bytes accessed", 0.0))
    flops = flops_raw + extra["flops"]
    bytes_accessed = bytes_raw + extra["bytes"]
    coll_total = float(sum(coll.values()))
    # HBM traffic proxy: resident args + outputs + 2× temp churn.  The
    # operand-sum "bytes accessed" counts pre-fusion operand bytes and
    # overstates HBM traffic by ~10-100×; memory_analysis sizes are what
    # actually lives in (and must cross) HBM.
    hbm_bytes = 0.0
    if mem is not None:
        hbm_bytes = (float(getattr(mem, "argument_size_in_bytes", 0))
                     + float(getattr(mem, "output_size_in_bytes", 0))
                     + 2.0 * float(getattr(mem, "temp_size_in_bytes", 0)))
    # roofline terms are whole-step seconds: per-device work / per-chip peak
    rec.update({
        "hlo_flops_raw": flops_raw,
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "hbm_bytes": hbm_bytes,
        "collective_bytes": coll_total,
        "collectives": coll,
        "per_stage": per_stage,
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_total / ICI_BW,
        "model_flops": model_flops(cfg, shape),
        "lower_compile_s": round(time.time() - t0, 1),
    })
    total_flops = flops * n_chips
    rec["useful_flops_frac"] = (rec["model_flops"] / total_flops
                                if total_flops else 0.0)
    terms = {k: rec[k] for k in ("compute_s", "memory_s", "collective_s")}
    rec["bottleneck"] = max(terms, key=terms.get)
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                rec[f"mem_{attr}"] = int(v)
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {rec['mesh']}: "
              f"compile ok in {rec['lower_compile_s']}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={flops:.3e} bytes={bytes_accessed:.3e}")
        print(f"  collectives: {coll}")
        print(f"  roofline: compute={rec['compute_s']:.3e}s "
              f"memory={rec['memory_s']:.3e}s "
              f"collective={rec['collective_s']:.3e}s "
              f"-> {rec['bottleneck']}")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful pre-optimisation sharding")
    ap.add_argument("--all", action="store_true",
                    help="every assigned arch × shape")
    ap.add_argument("--out", default=None, help="append jsonl here")
    args = ap.parse_args(argv)

    combos = []
    if args.all:
        combos = [(a, s) for a in ASSIGNED_ARCHS for s in INPUT_SHAPES]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape (or --all) required")
        combos = [(args.arch, args.shape)]

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    failures = []
    for arch, shape in combos:
        try:
            rec = dryrun_one(arch, shape, multi_pod=args.multi_pod,
                             mesh=mesh, baseline=args.baseline)
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            rec = {"arch": arch, "shape": shape, "error": repr(e)[:500],
                   "mesh": "x".join(map(str, mesh.devices.shape))}
            failures.append((arch, shape, repr(e)[:200]))
            print(f"[dryrun] FAIL {arch} × {shape}: {repr(e)[:200]}")
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for a, s, e in failures:
            print(f"  {a} × {s}: {e}")
        return 1
    print(f"\nall {len(combos)} combos compiled OK "
          f"on mesh {'x'.join(map(str, mesh.devices.shape))}")
    return 0


if __name__ == "__main__":
    # 512 placeholder host devices; set before the first backend use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    sys.exit(main())

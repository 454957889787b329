"""Hillclimb harness: lower one (arch × shape), print roofline terms and the
top collective contributors (trip-scaled), so each hypothesis→change cycle
has an op-level profile to reason from.

  PYTHONPATH=src python experiments/hillclimb.py llama3-405b train_4k
"""
import os
import re
import sys

import jax

from repro.launch.dryrun import (_computations, _shape_bytes, _TRIP_RE,
                                 _WHILE_BODY_RE, _COLLECTIVES, dryrun_one)


def top_collectives(hlo_text: str, k: int = 14):
    comps = _computations(hlo_text)
    # computation -> multiplier (product of enclosing trip counts)
    mult = {"__entry__": 1}
    frontier = ["__entry__"]
    while frontier:
        name = frontier.pop()
        text = comps.get(name, "")
        for line in text.splitlines():
            if " while(" not in line:
                continue
            mb = _WHILE_BODY_RE.search(line)
            mt = _TRIP_RE.search(line)
            if mb and mb.group(1) in comps:
                trip = int(mt.group(1)) if mt else 1
                mult[mb.group(1)] = mult.get(name, 1) * trip
                frontier.append(mb.group(1))
    rows = []
    for name, text in comps.items():
        if name == "__entry__" or name not in mult:
            m = mult.get(name)
            if m is None:
                continue
        m = mult[name]
        for line in text.splitlines():
            ls = line.strip()
            mm = re.search(r"=\s+((?:\([^)]*\)|\S+))\s+([\w-]+)", ls)
            if not mm:
                continue
            op = mm.group(2).rstrip(".0123456789")
            if op in _COLLECTIVES:
                b = _shape_bytes(mm.group(1)) * m
                meta = re.search(r'op_name="([^"]*)"', ls)
                rows.append((b, op, mm.group(1)[:60], m,
                             (meta.group(1)[-70:] if meta else "")))
    rows.sort(reverse=True)
    return rows[:k]


def main():
    arch, shape = sys.argv[1], sys.argv[2]
    import repro.launch.dryrun as dr
    # optional ParallelConfig overrides: key=value pairs after the shape
    if len(sys.argv) > 3:
        import dataclasses
        from repro.configs import ARCH_REGISTRY, get_config
        cfg = get_config(arch)
        kw = {}
        for kv in sys.argv[3:]:
            k, v = kv.split("=")
            kw[k] = {"True": True, "False": False}.get(v) \
                if v in ("True", "False") else (int(v) if v.isdigit() else v)
        cfg = cfg.with_(parallel=dataclasses.replace(cfg.parallel, **kw))
        ARCH_REGISTRY[arch] = cfg
        print(f"overrides: {kw}")
    # capture the HLO text by monkey-wrapping collective_bytes_scaled
    captured = {}
    orig = dr.collective_bytes_scaled

    def wrap(text):
        captured["hlo"] = text
        return orig(text)

    dr.collective_bytes_scaled = wrap
    rec = dryrun_one(arch, shape, verbose=False)
    dr.collective_bytes_scaled = orig
    print(f"== {arch} × {shape} ==")
    for kk in ("compute_s", "memory_s", "collective_s", "bottleneck",
               "hlo_flops", "hbm_bytes", "collective_bytes",
               "useful_flops_frac"):
        print(f"  {kk}: {rec[kk]}")
    print("\ntop collectives (trip-scaled bytes):")
    for b, op, shp, m, meta in top_collectives(captured["hlo"]):
        print(f"  {b/1e9:9.1f} GB  x{m:<4d} {op:20s} {shp:60s} {meta}")


if __name__ == "__main__":
    # 512 placeholder host devices; set before the first backend use
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()

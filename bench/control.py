"""Readings that set a cell's correctness limit (not part of a run).

    python3 bench/control.py --workload <name> --seconds <s> --seeds <n,...>

For each seed, in one process: serve the cell at its own load for a short
window (no shape warm-up: compiles inside it do not matter here), sample
the finished requests as a run does, and read the widest logit gap of the
tokens the program served against the float32 reference (the lower
reading) and of the tokens the control would serve: the reference itself
computed with int8 or float8 operands, its greedy choice at each position
of the same prompts and tokens (the upper reading).  One JSON line per
seed on standard output.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

CONTROLS = ("int8", "fp8")


def readings(run, seed: int, controls=CONTROLS) -> dict:
    from harness import correct
    picked = correct.sample(run.window.outcomes, run.results, seed,
                            run.mix["reference_requests"])
    prompts = [o.spec.tokens for o in picked]
    served = [run.results[o.rid] for o in picked]
    ref = correct.reference_module(run.cfg).served_logits(
        run.cfg, seed % (2 ** 31), prompts, served, ("f32",) + controls)
    out = {"seed": seed, "requests": len(picked),
           "tokens": int(sum(len(s) for s in served))}
    for kind, keep in (("shared", lambda o: o.shared_base > 0),
                       ("unshared", lambda o: o.shared_base == 0)):
        g = [float(correct.gaps(lg, s).max()) for lg, s, o in
             zip(ref["f32"], served, picked) if keep(o)]
        out[f"program_{kind}"] = max(g, default=0.0)
    for prec in controls:
        out[f"control_{prec}"] = max(
            float(correct.gaps(lf, lc.argmax(-1)).max())
            for lf, lc in zip(ref["f32"], ref[prec]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    import run as run_mod
    from harness import cell
    run_mod.configure_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.time()
        run = cell.run_cell(args.workload, seed, args.seconds,
                            trace_dir=None, process_start=t,
                            mix_overrides={"prefill_rows": []})
        line = readings(run, seed)
        line["seconds"] = time.time() - t
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

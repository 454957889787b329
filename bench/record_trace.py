"""Record a traced window of one cell, keep its trace, and reduce the
program's host spans in it.

    python3 bench/record_trace.py --workload <name> --seed <n>
                                  --seconds <s> --out <file.xplane.pb>

Runs the cell as ``bench/run.py --trace 1`` does (set-up, warm-up, the
window under the profiler), keeps the window's trace at ``--out``, and
prints one JSON line: the cell's per-layer metrics and ``tokens_per_s``,
read by the benchmark's own readers from the traced window; from the
program's host spans (``harness/spans.py``), the first chip's idle
seconds by the innermost span they fell in, the executor's and decoder's
shares of the window among them, the three longest idle gaps with that
split of each, the share of the decoder's reserved KV pages in use over
its decode launches and of padded prefill positions that carried prompt
tokens, and every span's stats summed over the window; and the run's
pace.  No correctness check runs, so this is a measuring tool, not a
benchmark run.  Without a TPU it prints nothing and exits 3.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys

import run as bench_run                  # puts bench/ and src/ on the path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from harness import cell as cell_mod, peaks, report, spans
    from harness.trace import Trace
    bench = cell_mod.load_benchmark()
    cell = cell_mod.find_cell(bench, args.workload)
    bench_run.configure_compile_cache()
    err = bench_run.require_chips(cell)
    if err:
        print(f"[record] {args.workload}: {err}", file=sys.stderr)
        return 3
    trace_dir = str(bench_run.CHECKOUT / ".bench_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    run = cell_mod.run_cell(args.workload, args.seed, args.seconds,
                            trace_dir=trace_dir,
                            process_start=bench_run.process_start(),
                            bench=bench)
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copyfile(found[-1], args.out)
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace = Trace.from_file(args.out)
    host = spans.read(args.out)
    ctx = report.Context(run, trace, peaks.peaks(run.device["kind"]))
    names = [m["name"] for m in report.cell_metrics(bench, "per_layer",
                                                    args.workload)]
    metrics = {n: report.reader(n)(ctx) for n in names + ["tokens_per_s"]}
    result = {
        "metrics": metrics,
        "device_idle_share.executor": spans.idle_share(
            trace, host, "repro.executor."),
        "device_idle_share.decoder": spans.idle_share(
            trace, host, "repro.decoder."),
        "kv_page_use_share": spans.kv_page_use_share(trace, host),
        "prefill_useful_share": spans.prefill_useful_share(trace, host),
        "idle_s_by_span": dict(sorted(
            spans.idle_by_span(trace, host).items(), key=lambda x: -x[1])),
        "longest_idle_gaps": sorted(spans.idle_gaps_by_span(trace, host),
                                    key=lambda g: -g[0])[:3],
        "stat_sums": spans.stat_sums(trace, host),
        "window_s": trace.window_s, "busy_s": trace.busy_s(),
        "programs": sorted({e.name.split("(")[0] for e in
                            trace.events("XLA Modules")}),
        "pace": report.pace(run.window), "device": run.device}
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())

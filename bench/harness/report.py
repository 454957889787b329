"""From a finished run to the result line.

The end-to-end metrics (``--trace 0``) and the per-layer metrics
(``--trace 1``) a cell reports are the entries of ``BENCHMARK.json`` whose
``workloads`` list names it (or that have none); each is read by its own
file ``bench/metrics/<name>.py``, whose ``read(ctx)`` returns the value or
``None`` where it finds nothing to read (the metric is then left out).
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional

from . import cell as cell_mod, correct, peaks as peaks_mod
from .trace import Trace

METRICS_DIR = cell_mod.BENCH_DIR / "metrics"
TOP = 10


@dataclass
class Context:
    """What a metric reader can read."""
    run: "cell_mod.Run"
    trace: Optional[Trace]
    peaks: Optional[dict]

    @property
    def window(self):
        return self.run.window

    @property
    def cfg(self) -> dict:
        return self.run.cfg

    def due_in_window(self) -> list:
        w = self.window
        return [o for o in w.outcomes if w.t0 <= o.due < w.t_stop]

    def traced_steps(self) -> list:
        """Steps wholly inside the traced window (harness clock)."""
        w = self.window
        return [s for s in w.steps
                if s.t_call >= w.trace_t0 and s.t_return <= w.trace_t1]

    def record(self, rid: int):
        """The scheduler's record of request ``rid`` (None if it has
        none)."""
        if not hasattr(self, "_records"):
            self._records = {r.request_id: r for r in self.window.records}
        return self._records.get(rid)


def reader(name: str):
    path = METRICS_DIR / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, section: str, workload: str) -> List[dict]:
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def failed(o, results: Dict[int, list], record) -> bool:
    """A request due in the window that was refused, timed out, never
    finished, or finished short."""
    if record is not None and record.outcome != "done":
        return True
    return len(results.get(o.rid, ())) != o.spec.decode_tokens


def pace(w) -> dict:
    """Where the window's wall time went on the host, to tell a slow run's
    cause: steps completed, seconds inside the step function and between
    its calls, the longest step, the median wall time of a step that
    admitted requests and of one that only decoded, this process's CPU
    seconds and garbage-collector pauses, and the host's load average."""
    inside = [s for s in w.steps if w.t0 <= s.t_call and s.t_return <= w.t_stop]
    step_s = sum(s.t_return - s.t_call for s in inside)
    out = {"steps_in_window": len(inside), "step_fn_s": step_s,
           "between_steps_s": (w.t_stop - w.t0) - step_s}
    if inside:
        out["step_ms_max"] = 1e3 * max(s.t_return - s.t_call for s in inside)
    for kind, keep in (("admit", True), ("decode", False)):
        ms = [1e3 * (s.t_return - s.t_call) for s in inside
              if bool(s.prefill_rows) is keep]
        if ms:
            out[f"{kind}_step_ms_median"] = statistics.median(ms)
    out.update(cpu_s=w.cpu_s, gc_s=w.gc_s, host_load_1m=os.getloadavg()[0])
    return out


def run_and_report(bench: dict, workload: str, seed: int, seconds: float,
                   *, trace_dir: Optional[str], process_start: float,
                   **test_kw) -> dict:
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = cell_mod.run_cell(workload, seed, seconds, trace_dir=trace_dir,
                            process_start=process_start, bench=bench,
                            **test_kw)
    picked = correct.sample(run.window.outcomes, run.results, seed,
                            run.mix["reference_requests"])
    checks, info = correct.check(run.cfg, seed % (2 ** 31), picked,
                                 run.results, run.window.steps)
    trace = Trace.from_dir(trace_dir) if trace_dir else None
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    kind = run.device["kind"]
    pk = peaks_mod.peaks(kind) if run.device["platform"] == "tpu" else None
    ctx = Context(run, trace, pk)
    due = ctx.due_in_window()
    n_failed = sum(failed(o, run.results, ctx.record(o.rid)) for o in due)
    section = "per_layer" if trace_dir else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, section, workload):
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(run.device)
    result = {"correct": correct.passed(checks), "attempted": len(due),
              "failed": n_failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
        ops = sorted(trace.op_seconds().items(), key=lambda x: -x[1])
        idle: Dict[str, float] = {}
        for name, s in trace.idle_gaps():
            idle[name] = idle.get(name, 0.0) + s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in ops[:TOP]],
            "idle_gaps": sorted(([n, s] for n, s in idle.items()),
                                key=lambda x: -x[1])[:TOP]}
    result["diagnostics"] = dict(
        info, setup_phases_s=run.phases, warm_prefill_shapes=run.warm_shapes,
        compiles_in_window=run.window.compiles, **pace(run.window))
    result["checks"] = checks
    return result


def emit(result: dict) -> None:
    """The compared numbers with their limits, last on standard error; the
    result, last on standard output.  A metric that is not a finite number
    (a tail over requests of which too many failed) ends the run without a
    result."""
    bad = {n: m["value"] for n, m in result["metrics"].items()
           if not math.isfinite(m["value"])}
    if bad:
        raise SystemExit(f"[bench] metrics without a finite value: {bad}; "
                         f"{result['failed']} of {result['attempted']} "
                         f"requests failed")
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False))
    sys.stdout.flush()

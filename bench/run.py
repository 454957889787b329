"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n>
                         --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration and a
traffic mix.  The run builds the program's served path with one worker on
this process's accelerator, warms up every shape the traffic can produce
(counted as set-up), serves the traffic for ``--seconds``, checks what the
served path produced against the plain reference, and prints one JSON
object as the last line of standard output: with ``--trace 0`` the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  The numbers compared for ``correct`` are
printed with their limits as the last lines of standard error and, last,
in the result line.

A run that finds no TPU, or fewer chips than the cell asks for, prints no
result and exits 3.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.time()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
sys.path[:0] = [str(BENCH), str(CHECKOUT / "src")]


def process_start() -> float:
    """Wall time at which this process started (Linux), else the time the
    interpreter reached this module."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return PROCESS_T0


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<checkout>/.jax_cache`` (a fixed path: the path is part of
    the cache key).  Every program is cached, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(cell: dict) -> str:
    """The device check: a TPU, with at least the chips the cell asks for.
    Returns an error message, or '' when the device will do."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        return (f"needs a TPU; JAX found {devs[0].platform} "
                f"({devs[0].device_kind})")
    if len(devs) < cell["chips"]:
        return f"needs {cell['chips']} chips; JAX found {len(devs)}"
    return ""


def main(argv=None) -> int:
    args = parse_args(argv)
    from harness import cell as cell_mod
    from harness import report
    bench = cell_mod.load_benchmark()
    cell = cell_mod.find_cell(bench, args.workload)
    configure_compile_cache()
    err = require_chips(cell)
    if err:
        print(f"[bench] {args.workload}: {err}; no result", file=sys.stderr)
        return 3
    out_dir = CHECKOUT / ".bench_trace" if args.trace else None
    result = report.run_and_report(
        bench, args.workload, args.seed, args.seconds,
        trace_dir=str(out_dir) if out_dir else None,
        process_start=process_start())
    report.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, the measured window, the correctness check.

Everything that belongs to a configuration, a traffic mix or a per-layer
metric is found by name: ``BENCHMARK.json`` names the cell, the cell names
its configuration (``configs[].file``) and traffic (``bench/traffic/``),
and each per-layer metric is read by ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import gc
import json
import pathlib
import time
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import jax

from . import loops, traffic as traffic_mod
from .served import PAGE, ServedPool

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parent
WARM_SETTLE_S = 0.05        # gap between the end of set-up and the window


def load_benchmark() -> dict:
    with open(CHECKOUT / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_config(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(CHECKOUT / c["file"]) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


# the program's ModelConfig field each configuration key must equal
_PROGRAM_FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def program_config(cfg: dict):
    """The program's ModelConfig for configuration ``cfg``: its registered
    architecture cut to ``num_hidden_layers``, checked key by key, so the
    program runs what the configuration states or the run stops."""
    from repro.configs import get_config
    mc = get_config(cfg["program_arch"])
    mc = mc.with_(n_layers=cfg["num_hidden_layers"])
    bad = {k: (cfg[k], getattr(mc, f)) for k, f in _PROGRAM_FIELDS.items()
           if cfg[k] != getattr(mc, f)}
    if (mc.family != "dense" or mc.qk_norm or mc.attn_window
            or mc.attn_logit_softcap or mc.kv_cache_dtype):
        bad["block"] = "not a plain dense GQA block"
    if bad:
        raise ValueError(f"program departs from {cfg['name']}: {bad}")
    return mc


def smoke_config(cfg: dict):
    """Test-only: the program's 2-layer smoke preset of ``cfg``'s
    architecture, and the configuration dict that describes it."""
    from repro.configs import get_smoke_config
    mc = get_smoke_config(cfg["program_arch"])
    small = dict(cfg, hidden_size=mc.d_model, intermediate_size=mc.d_ff,
                 num_hidden_layers=mc.n_layers, num_attention_heads=mc.n_heads,
                 num_key_value_heads=mc.n_kv_heads,
                 head_dim=mc.resolved_head_dim, vocab_size=mc.vocab_size)
    return mc, small


@dataclass
class Window:
    """What the measured window produced, on the harness's clock."""
    t0: float
    t_stop: float
    outcomes: list
    steps: list
    records: list
    compiles: int
    trace_dir: Optional[str] = None
    trace_t0: float = 0.0           # harness clock at the trace markers
    trace_t1: float = 0.0
    cpu_s: float = 0.0              # this process's CPU time in the window
    gc_s: float = 0.0               # garbage-collector pauses in the window


class CompileCounter:
    """Counts programs lowered (traced for compilation) while active."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        self.active = False
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if self.active and event == self.EVENT:
            self.n += 1


class GcPauses:
    """Seconds the interpreter's garbage collector held this process from
    construction to ``close``, from ``gc.callbacks``."""

    def __init__(self):
        self.s = 0.0
        self.active = True
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.active:
            self.s += time.perf_counter() - self._t

    def close(self) -> None:
        self.active = False
        gc.callbacks.remove(self._on)


def measure_window(pool: ServedPool, mix: dict, seconds: float,
                   trace_dir: Optional[str],
                   compiles: CompileCounter) -> Window:
    """Serve the cell's traffic for ``seconds``; trace it when ``trace_dir``
    is given.  The window closes (trace stopped, compile count frozen) at
    the first step boundary past ``t_stop``; the pool then drains, and what
    it finishes late still counts as finished, late."""
    clock = pool.clock
    t0 = clock() + WARM_SETTLE_S
    t_stop = t0 + seconds
    first_step = len(pool.steps)
    first_index = pool._next_index
    marks: Dict[str, float] = {}
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window_start"):
        marks["t0"] = clock()
    marks["cpu0"] = time.process_time()
    compiles.active = True
    pauses = GcPauses()

    def close(force: bool = False) -> None:
        if "t1" in marks or not (force or clock() >= t_stop):
            return
        compiles.active = False
        pauses.close()
        with jax.profiler.TraceAnnotation("bench.window_end"):
            marks["t1"] = clock()
        marks["cpu1"] = time.process_time()
        if trace_dir:
            jax.profiler.stop_trace()

    loops.closed_window(pool, mix["outstanding"], t0, t_stop, close)
    if "t1" not in marks:               # the pool went idle before t_stop
        loops.wait_until(clock, t_stop)
        close(force=True)
    outcomes = [o for o in pool.outcomes.values()
                if o.spec.index >= first_index]
    return Window(t0, t_stop, outcomes, pool.steps[first_step:],
                  list(pool.app.records()), compiles.n, trace_dir,
                  marks["t0"], marks["t1"], marks["cpu1"] - marks["cpu0"],
                  pauses.s)


@dataclass
class Run:
    """A finished run: its inputs, set-up times and the window."""
    cell: dict
    cfg: dict                 # the configuration as run
    mix: dict
    seed: int
    seconds: float
    setup_s: float
    window: Window
    device: dict
    results: Dict[int, list]  # request id -> served token ids
    phases: Dict[str, float]
    warm_shapes: int


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_cell(workload: str, seed: int, seconds: float, *,
             trace_dir: Optional[str], process_start: float,
             smoke: bool = False, device_name: Optional[str] = None,
             mix_overrides: Optional[dict] = None,
             break_path: Optional[Callable[[ServedPool], None]] = None,
             bench: Optional[dict] = None) -> Run:
    """Set up, warm up and measure one cell.  ``smoke``, ``device_name``,
    ``mix_overrides`` and ``break_path`` are for the benchmark's own tests
    on the CPU: the 2-layer preset, a catalog device to describe the
    worker by, smaller warm-up, and a fault planted in the served path.
    ``bench`` is ``BENCHMARK.json`` as loaded (read from the checkout when
    not given)."""
    bench = bench or load_benchmark()
    cell = find_cell(bench, workload)
    cfg = load_config(bench, cell["config"])
    if smoke:
        model_cfg, cfg = smoke_config(cfg)
    else:
        model_cfg = program_config(cfg)
    mix = dict(traffic_mod.load_mix(cell["traffic"]),
               **(mix_overrides or {}))
    traffic = traffic_mod.Traffic(mix, seed, cfg["vocab_size"])
    lo, hi = traffic.prompt_lengths()
    if hi > mix["prompt_len"] or mix["prompt_len"] + max(
            c["decode_tokens"] for c in mix["classes"]) > mix["max_len"]:
        raise ValueError(f"traffic {cell['traffic']}: prompts of {lo}-{hi} "
                         f"tokens do not fit prompt_len/max_len")
    compiles = CompileCounter()
    phases = {}
    t = time.perf_counter()
    pool = ServedPool(model_cfg, mix, traffic, weight_seed=seed % (2 ** 31),
                      device_name=device_name)
    if break_path is not None:
        break_path(pool)
    # a burst fills the pool to its slot budget (the decode program at the
    # pool's final capacity)
    loops.closed_burst(pool, mix["warmup_requests"])
    phases["serve_warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm = pool.warm_prefill_shapes(mix["prefill_rows"],
                                    traffic_mod.prefill_buckets(mix, lo, hi,
                                                                PAGE))
    phases["prefill_shapes_s"] = time.perf_counter() - t
    setup_s = time.time() - process_start + WARM_SETTLE_S
    window = measure_window(pool, mix, seconds, trace_dir, compiles)
    dev = device_info()
    dev["memory_peak_bytes"] = peak_bytes()
    results = {o.rid: list(pool.ex.results.get(o.rid, []))
               for o in window.outcomes}
    # free the program's state before the reference runs on the device
    pool.payloads = None
    for w in pool.sched.workers.values():
        for lib in w.libraries.values():
            if lib.context is not None:
                lib.teardown()
    del pool
    gc.collect()
    return Run(cell, cfg, mix, seed, seconds, setup_s, window, dev, results,
               phases, warm)

"""Serving driver: opportunistic throughput-oriented inference, live.

Runs the Prompt-for-Fact application through the REAL context-management
stack on this host: a pool of workers (all sharing this process's
device, each described by that device's catalog entry) is driven by the
LiveExecutor; contexts are really materialised (imports, weights, jit)
and really reused.  The model runs at its published widths unless
``--smoke`` asks for the 2-layer smoke preset (CPU tests, CI).

Two submission modes:

* ``--stream`` (default) — the request-stream API: one request per claim
  with a decode-step budget; resident libraries continuously admit
  requests into their in-flight batch (the padded JAX batch is re-formed
  between steps with bucketed shapes).  Reports throughput AND the
  per-request latency distributions (queue wait, time-to-first-step).
* ``--batch-tasks`` — the deprecated run-to-completion batch path (the
  paper's original pv2/pv4 shape), kept as the comparison baseline.

  PYTHONPATH=src python -m repro.launch.serve --claims 64 --workers 2
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --smoke \
      --device "NVIDIA A10" --claims 24 --workers 2

JAX's persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time
from dataclasses import dataclass
from typing import Any, List, Optional

import jax

from repro.cluster import (Application, ClassPolicy, Gateway, LiveExecutor,
                           Scheduler, Worker, format_class_latency,
                           format_gateway, format_pool, format_zone_bytes,
                           local_device_model, pool_summary)
from repro.configs import ModelConfig, get_config, get_smoke_config
from repro.core import MODES
from repro.data import accuracy, claim_batches, generate_claims
from repro.data.tokenizer import ByteTokenizer
from repro.inference import (MAX_NEW, build_context_recipe, infer_claims,
                             make_pff_step_fn, stream_verdict)

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR``
    when it is set (JAX reads it itself) and at ``<checkout>/.jax_cache``
    otherwise.  Called by entry points only, never on import.  Returns the
    directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm2-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the 2-layer smoke preset of --arch instead "
                         "of its published widths (CPU tests, CI)")
    ap.add_argument("--device", default=None, metavar="NAME",
                    help="catalog entry describing the workers' device; "
                         "default: the entry for this process's "
                         "accelerator (a CPU run must name one)")
    ap.add_argument("--claims", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8,
                    help="claims per task in --batch-tasks mode")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--mode", default="pervasive",
                    choices=sorted(MODES))
    ap.add_argument("--template", default="with_evidence")
    group = ap.add_mutually_exclusive_group()
    group.add_argument("--stream", action="store_true", default=True,
                       help="request-stream API with continuous batching "
                            "(default)")
    group.add_argument("--batch-tasks", dest="stream",
                       action="store_false",
                       help="deprecated run-to-completion batch tasks")
    ap.add_argument("--interactive-every", type=int, default=0,
                    metavar="N",
                    help="mark every Nth claim INTERACTIVE (deadline'd, "
                         "may preempt batch slots); 0 = all batch class")
    ap.add_argument("--deadline", type=float, default=60.0,
                    help="relative queue deadline for interactive "
                         "requests (seconds)")
    return ap.parse_args(argv)


@dataclass
class ServeRun:
    """What one :func:`serve` call built and produced."""
    args: argparse.Namespace
    cfg: ModelConfig
    claims: List[Any]
    sched: Scheduler
    app: Application
    ex: LiveExecutor
    gateway: Optional[Gateway]
    preds: List[str]
    wall_s: float


def serve(args: argparse.Namespace) -> ServeRun:
    """Build the pool and serve ``args.claims`` claims to completion."""
    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    device = local_device_model(args.device)
    claims = generate_claims(args.claims, seed=1)
    recipe = build_context_recipe(cfg, args.template)
    mode = MODES[args.mode]
    if args.stream and not mode.state_resident:
        # continuous batching presupposes a resident context; the
        # partial/naive baselines only exist as run-to-completion tasks
        print(f"[serve] mode={args.mode} is not state-resident; "
              f"falling back to --batch-tasks")
        args.stream = False

    sched = Scheduler()
    app = Application(sched, default_mode=mode)
    key = app.register(recipe)
    for _ in range(args.workers):
        sched.add_worker(Worker(device, zone="z0"))

    t0 = time.perf_counter()
    if args.stream:
        # the serving gateway fronts every stream submission: SLO classes,
        # bounded queues, deadline semantics (all-batch traffic passes
        # through untouched — the batch class queues unbounded)
        gw = Gateway(sched, interactive=ClassPolicy(
            max_queue=64, overflow="reject", deadline_s=args.deadline))
        ex = LiveExecutor(sched, step_fns={key: make_pff_step_fn()})
        every = args.interactive_every
        for i, c in enumerate(claims):
            slo = ("interactive" if every and (i % every == 0)
                   else "batch")
            app.submit(key, decode_steps=MAX_NEW, payload=c,
                       arrival_s=ex.now(), slo=slo)
        ex.run()
        tok = ByteTokenizer(cfg.vocab_size)
        preds = [stream_verdict(tok, ex.results[r.request_id])
                 for r in app.requests
                 if r.request_id in ex.results]
    else:
        gw = None
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from repro.cluster.scheduler import Task
            for b in claim_batches(claims, args.batch):
                sched.submit(Task(key, len(b), mode, payload=b))
        ex = LiveExecutor(sched, {key: infer_claims})
        ex.run()
        preds = []
        for tid in sorted(ex.results):
            preds.extend(ex.results[tid])
    return ServeRun(args, cfg, claims, sched, app, ex, gw, preds,
                    time.perf_counter() - t0)


def report(run: ServeRun) -> None:
    """Print the run summary: throughput, latencies, pool, plane bytes."""
    sched, dt, n_done = run.sched, run.wall_s, len(run.preds)
    acc = accuracy(run.preds, run.claims)
    recs = sched.records
    cold = [r.exec_s for r in recs if not r.warm]
    warm = [r.exec_s for r in recs if r.warm]
    api = "stream" if run.args.stream else "batch-tasks"
    print(f"[serve] api={api} mode={run.args.mode} workers={run.args.workers} "
          f"claims={len(run.claims)} arch={run.cfg.arch_id} "
          f"layers={run.cfg.n_layers} d_model={run.cfg.d_model}")
    print(f"  wall {dt:.2f}s  throughput {n_done/dt:.1f} inf/s  "
          f"accuracy {acc:.3f}")
    if cold:
        print(f"  cold requests: {len(cold)}  "
              f"mean {sum(cold)/len(cold):.2f}s")
    if warm:
        print(f"  warm requests: {len(warm)}  "
              f"mean {sum(warm)/len(warm):.3f}s")
    if run.gateway is not None:
        print(format_class_latency(run.app.class_latency_summary()))
        print(format_gateway(run.gateway))
        # supply-side view: per-class joins/evictions (no factory in the
        # live path — target/lead-time rows appear only under one)
        print(format_pool(pool_summary(sched)))
        print(f"  admissions into live batches: {sched.admissions}  "
              f"preemptions: {sched.preemptions}")
    # context-plane run summary: per-zone transfer bytes + op counters
    print(format_zone_bytes(sched.plane))


def main(argv=None) -> int:
    args = parse_args(argv)
    configure_compile_cache()
    report(serve(args))
    return 0


if __name__ == "__main__":
    sys.exit(main())

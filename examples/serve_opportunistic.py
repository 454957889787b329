"""Opportunistic serving, live: eviction mid-run, the context follows.

Starts the PfF application on one worker; after a third of the work the
worker is EVICTED with no grace period (its running task is requeued, its
hosted context is lost).  A fresh opportunistic joiner takes over: the
scheduler re-stages the context there once and completes the run — the
paper's Challenge #1 handled by design, live.

Both workers run on this process's device and are described by its
catalog entry; a CPU run names the device it stands in for:

  JAX_PLATFORMS=cpu PYTHONPATH=src python examples/serve_opportunistic.py \
      --device "NVIDIA A10"
"""
import argparse
import os
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.cluster import (LiveExecutor, Scheduler, Worker,
                           local_device_model)
from repro.cluster.scheduler import Task
from repro.configs import get_smoke_config
from repro.core import PERVASIVE
from repro.data import accuracy, claim_batches, generate_claims
from repro.inference import build_context_recipe, infer_claims


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--device", default=None, metavar="NAME",
                    help="catalog entry describing the workers' device; "
                         "default: the entry for this process's accelerator")
    device = local_device_model(ap.parse_args(argv).device)
    cfg = get_smoke_config("smollm2-1.7b")
    claims = generate_claims(48, seed=3)
    recipe = build_context_recipe(cfg, "zero_shot")

    sched = Scheduler()
    key = sched.register_context(recipe)
    w0 = Worker(device)
    sched.add_worker(w0)
    for b in claim_batches(claims, 8):
        sched.submit(Task(key, len(b), PERVASIVE, payload=b))

    ex = LiveExecutor(sched, {key: infer_claims})
    evicted = {"done": False}
    orig_route = sched.route

    def route_with_eviction():
        if (not evicted["done"]
                and sched.completed_inferences >= len(claims) // 3):
            requeued = sched.on_evict(w0.worker_id)
            joiner = Worker(device)
            sched.add_worker(joiner)
            evicted["done"] = True
            print(f"[pool] {w0.worker_id} EVICTED "
                  f"({len(requeued)} tasks requeued, context lost); "
                  f"{joiner.worker_id} joined cold")
            assert sched.registry.ready_workers(key) == set()
        return orig_route()

    sched.route = route_with_eviction
    ex.run()
    preds = [p for tid in sorted(ex.results) for p in ex.results[tid]]
    print(f"completed {sched.completed_inferences}/{len(claims)} "
          f"inferences, accuracy {accuracy(preds, claims):.3f}")
    for r in sorted(sched.records, key=lambda r: r.t_start):
        kind = "warm" if r.warm else "COLD"
        print(f"  task {r.task_id:2d}: {kind} {r.exec_s:6.2f}s "
              f"on {r.worker_id}")


if __name__ == "__main__":
    main()

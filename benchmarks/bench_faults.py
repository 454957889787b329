"""Crash-safe decode: checkpointed resume vs restart-fresh under crash storms.

Scenario (ROADMAP: failure-domain hardening): a decode-heavy request
stream on a small opportunistic pool with replacement supply, hit by a
seeded train of SILENT crash faults — no advance notice; only the
:class:`~repro.cluster.FailureDetector`'s heartbeat-lease expiry
converts each dead worker into an eviction (detection latency bounded by
the lease interval).  Two runs differ in exactly one knob:

* ``ckpt``    — ``ckpt_every_steps=CKPT_EVERY``: every settled batch
  member exports a bit-exact KV snapshot to a host in a different
  failure zone as a budget-checked ``KV_CKPT`` plane op; a crash victim
  with a landed checkpoint resumes from it, losing only the steps since;
* ``restart`` — ``ckpt_every_steps=None``: today's baseline, every
  crash victim restarts its decode from scratch.

Claims asserted in ``--smoke`` (and full) mode:

* equal completed work, strictly higher goodput (lower makespan) AND
  strictly fewer wasted decode tokens for the checkpointed run;
* every crash is detected within one lease interval of the fault;
* zero slot/page/byte leaks in both runs: nothing queued/running at the
  end, no plane op in flight, and the planned/moved byte meters agree
  exactly — including the KV_CKPT bytes (a drained run's in-flight
  checkpoints are refunded, so parity covers the checkpoint plane too);
* LIVE (this container's device): a decode stream checkpointed
  mid-flight and adopted by a fresh decoder continues TOKEN-EXACTLY vs
  an uninterrupted reference.

Usage: python -m benchmarks.run [--smoke] | python -m benchmarks.bench_faults [--smoke] [--seed N]
"""
from __future__ import annotations

import argparse
import sys
import time
from typing import Optional

from repro.cluster import (Application, FailureDetector, FaultInjector,
                           GPU_CATALOG, fault_schedule, format_zone_bytes,
                           make_sim)
from repro.core import WarmPoolPolicy

from .common import ACTIVE_PARAMS, RECIPE

A10 = GPU_CATALOG["NVIDIA A10"]
POOL_N = 6               # workers (3 zones x 2)
CKPT_EVERY = 8           # decode steps between KV checkpoint exports
LEASE_S = 20.0           # heartbeat lease: crash-detection bound
FIRST_FAULT_S = 40.0
FAULT_EVERY_S = 60.0
_EPS = 1e-6


def _assert_drained(sched, ex, label: str) -> None:
    """End-of-run accounting: nothing queued/running/in flight, no slot
    residue, and the plane's planned/moved byte meters agree exactly
    (KV_CKPT ops included — in-flight checkpoints of finished requests
    are refunded, so a drained run meters to parity)."""
    assert sched.done, f"[{label}] run did not drain"
    assert not sched.running, f"[{label}] requests stuck in running"
    assert all(not lane for lane in sched.lanes.values()), \
        f"[{label}] non-empty lane after drain"
    assert ex.pending_arrivals == 0, f"[{label}] arrivals never fired"
    for w in sched.workers.values():
        for lib in w.libraries.values():
            assert not lib.batch, \
                f"[{label}] slot leak on {w.worker_id}: {set(lib.batch)}"
    plane = sched.plane
    assert plane.inflight_ops == 0, \
        f"[{label}] {plane.inflight_ops} plane op(s) still in flight"
    assert plane.planned.as_dict() == plane.moved.as_dict(), \
        f"[{label}] byte leak: planned {plane.planned.as_dict()} != " \
        f"moved {plane.moved.as_dict()}"


def run_sim(ckpt_every: Optional[int], *, n_requests: int, decode_steps: int,
            n_faults: int, fault_workers: int, seed: int) -> dict:
    """One crash-storm run; returns its scorecard."""
    # replacement supply: the trace re-offers the pool ceiling every
    # 30 s, so crashed capacity comes back (as FRESH workers) while the
    # backlog drains — the opportunistic steady state
    horizon = FIRST_FAULT_S + n_faults * FAULT_EVERY_S + 3600.0
    trace = [(30.0 * i, POOL_N) for i in range(int(horizon / 30.0))]
    sched, ex, fac = make_sim(devices=[A10] * 4, trace=trace,
                              workers_per_zone=2,
                              warm_pool=WarmPoolPolicy(),
                              ckpt_every_steps=ckpt_every,
                              retry_seed=seed)
    app = Application(sched)
    key = app.register(RECIPE, active_params=ACTIVE_PARAMS)
    app.submit_stream(ex, [dict(recipe_key=key, decode_steps=decode_steps,
                                arrival_s=i * 0.1)
                           for i in range(n_requests)])
    det = FailureDetector(ex, lease_s=LEASE_S)
    faults = fault_schedule(FIRST_FAULT_S, FAULT_EVERY_S, n_faults,
                            "crash", fault_workers)
    inj = FaultInjector(ex, faults, detector=det, seed=seed)
    inj.arm()
    t0 = time.time()
    makespan = ex.run()
    label = f"ckpt={ckpt_every}"
    _assert_drained(sched, ex, label)
    for wid, cause, t_fault, t_detect in det.detection_log:
        assert cause != "crash" or t_detect - t_fault <= LEASE_S + _EPS, \
            f"[{label}] crash on {wid} detected {t_detect - t_fault:.1f}s " \
            f"after the fault (> lease {LEASE_S}s)"
    return {
        "label": label, "makespan": makespan,
        "completed": sched.completed_inferences,
        "wasted": sched.evicted_inferences,
        "ckpts": sched.kv_ckpts, "ckpt_resumes": sched.ckpt_resumes,
        "ckpts_deferred": sched.kv_ckpts_deferred,
        "crashes": sched.evictions_by_cause.get("crash", 0),
        "detections": len(det.detection_log),
        "kv": sched.plane.kv_summary(), "sched": sched,
        "wall_s": time.time() - t0,
    }


def run_live(*, n_steps: int = 24, crash_at: int = 10) -> None:
    """LIVE bit-exactness: checkpoint a decode mid-flight, adopt the
    snapshot into a FRESH decoder (the checkpoint host), and verify the
    resumed stream's tokens equal an uninterrupted reference's."""
    import jax
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.inference.streaming import StreamingDecoder
    from repro.models import model as M

    cfg = get_smoke_config("smollm2-1.7b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompt = list(rng.integers(4, cfg.vocab_size, 12))

    def mk():
        return StreamingDecoder(cfg, params, None, None, prompt_len=32,
                                max_len=64, page_size=8)

    ref = mk()
    ref.ensure_tokens(0, prompt)
    want = [ref.step([0])[0] for _ in range(n_steps)]

    src = mk()                       # the worker that will "crash"
    src.ensure_tokens(0, prompt)
    got = [src.step([0])[0] for _ in range(crash_at)]
    snap = src.checkpoint(0)         # non-destructive: src keeps decoding
    assert snap is not None, "no snapshot for a bound slot"
    assert src.pool.slot_of.get(0) is not None, \
        "checkpoint released the source slot"
    got += [src.step([0])[0] for _ in range(2)]   # steps LOST to the crash

    dst = mk()                       # the checkpoint host takes over
    dst.adopt(0, snap)
    dst.resume(0)
    resumed = [dst.step([0])[0] for _ in range(n_steps - crash_at)]
    assert got[:crash_at] + resumed == want, \
        "resumed stream diverged from the reference"
    assert dst.finish(0) == want, "finished token buffer diverged"
    assert dst.pages.in_use == 0, "page leak after finish"
    print(f"  [live] {crash_at} steps + crash + resume on fresh "
          f"decoder == {n_steps}-step reference (token-exact)")


def main(smoke: bool = False, seed: int = 3) -> None:
    sizes = dict(n_requests=48, decode_steps=256, n_faults=4,
                 fault_workers=3) if smoke else \
        dict(n_requests=160, decode_steps=384, n_faults=8, fault_workers=3)
    ckpt = run_sim(CKPT_EVERY, seed=seed, **sizes)
    base = run_sim(None, seed=seed, **sizes)

    print(f"\n[bench_faults] crash storms: {sizes['n_faults']} x "
          f"{sizes['fault_workers']} workers, lease {LEASE_S:.0f}s, "
          f"seed {seed}")
    for r in (ckpt, base):
        goodput = r["completed"] / r["makespan"]
        print(f"  {r['label']:>10}: makespan {r['makespan']:8.1f}s | "
              f"goodput {goodput:6.1f} inf/s | wasted decode "
              f"{r['wasted']:6d} | crashes {r['crashes']} "
              f"(detected {r['detections']}) | ckpts {r['ckpts']} "
              f"({r['ckpt_resumes']} resume(s), "
              f"{r['ckpts_deferred']} deferred)")
    print(format_zone_bytes(ckpt["sched"].plane, label="ckpt"))

    assert ckpt["completed"] == base["completed"], \
        "runs completed different work"
    assert ckpt["crashes"] > 0 and base["crashes"] > 0, \
        "no crash ever hit the pool — the scenario is vacuous"
    assert ckpt["ckpt_resumes"] > 0, \
        "no crash victim ever resumed from a checkpoint"
    assert ckpt["makespan"] < base["makespan"], \
        f"checkpointed resume did not beat restart-fresh on goodput " \
        f"({ckpt['makespan']:.1f}s vs {base['makespan']:.1f}s)"
    assert ckpt["wasted"] < base["wasted"], \
        f"checkpointed resume did not waste fewer decode tokens " \
        f"({ckpt['wasted']} vs {base['wasted']})"
    print(f"  claims hold: equal work ({ckpt['completed']} inf), goodput "
          f"{ckpt['makespan']:.1f}s < {base['makespan']:.1f}s, waste "
          f"{ckpt['wasted']} < {base['wasted']}, detection <= lease")

    run_live()


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--seed", type=int, default=3,
                    help="fault-schedule + retry-jitter seed")
    args = ap.parse_args()
    main(smoke=args.smoke, seed=args.seed)
    sys.exit(0)

"""Serving gateway: interactive p95 under batch overload, with preemption.

The gateway tentpole claim: with SLO classes and deadline-driven batch
preemption, an INTERACTIVE trickle keeps its unloaded latency while the
cluster is saturated by 10x+ BATCH overload — and the batch class loses
no work (preempted requests suspend their KV state and resume without
re-prefill, so every submitted batch decode step still completes).

Three DES runs on an identical 4xA10 pool (16 decode slots):

* ``unloaded``  — the interactive trickle alone: the latency floor.
* ``baseline``  — the same trickle + batch flood, NO gateway: pure FIFO
  (interactive requests queue behind the whole backlog).
* ``gateway``   — same workload fronted by the :class:`Gateway`:
  deadline'd interactive heads preempt settled batch slots.

Reported: per-class p95 e2e over the steady-state window, completed
batch decode units, preemption/spill/resume counters.

The LIVE section drives a real :class:`StreamingDecoder` through the
suspend/resume path: a victim is suspended mid-decode, others keep
stepping, the victim resumes — and its token stream must be BIT-EXACT
against an uninterrupted run.  Slot and page accounting must balance to
zero afterwards.

``--smoke`` (the CI guard): FAILS if gateway interactive p95 exceeds
1.2x the unloaded p95, if the gateway run completes less batch work than
the FIFO baseline, if no preemption actually happened, if resumed tokens
diverge, or if any slot/page/byte accounting leaks.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

from repro.cluster import (Application, ClassPolicy, GPU_CATALOG, Gateway,
                           make_sim, percentile)

from .common import ACTIVE_PARAMS, RECIPE, Report

# -- sim scenario -----------------------------------------------------------
N_WORKERS = 4
SLOT_BYTES = 5_000_000_000        # pins 4 decode slots per 24 GB A10
BATCH_REQS = 320                  # ~10x overload vs the 16-slot pool
BATCH_STEPS = 48
BURST_T0, BURST_END, BURST_EVERY = 40.0, 300.0, 20.0
BURST_N, INT_STEPS = 4, 6
DEADLINE_S = 12.0                 # relative queue deadline (interactive)
MEASURE_FROM = 75.0               # skip the cold-start bursts (staging
                                  # runs until ~67s even unloaded)
UNTIL = 5_000.0


def _sim_pool():
    sched, ex, fac = make_sim(devices=[GPU_CATALOG["NVIDIA A10"]] * N_WORKERS,
                              workers_per_zone=N_WORKERS)
    app = Application(sched)
    # pin the decode-slot footprint so the slot budget is deterministic
    recipe = dataclasses.replace(RECIPE, slot_bytes=SLOT_BYTES)
    key = app.register(recipe, active_params=ACTIVE_PARAMS)
    return sched, ex, fac, app, key


def _interactive_specs(slo: str):
    out, t = [], BURST_T0
    while t <= BURST_END + 1e-9:
        out.extend(dict(decode_steps=INT_STEPS, arrival_s=t, slo=slo)
                   for _ in range(BURST_N))
        t += BURST_EVERY
    return out


def _batch_specs():
    return [dict(decode_steps=BATCH_STEPS, arrival_s=0.0, slo="batch")
            for _ in range(BATCH_REQS)]


def _run(name: str, *, with_batch: bool, with_gateway: bool):
    """One DES run; returns (sched, gateway, interactive ids, batch ids)."""
    sched, ex, fac, app, key = _sim_pool()
    gw = None
    if with_gateway:
        gw = Gateway(sched, interactive=ClassPolicy(
            max_queue=64, overflow="reject", deadline_s=DEADLINE_S,
            preempt_slack_s=DEADLINE_S))
    bids = set()
    if with_batch:
        bs = app.submit_stream(ex, [dict(s, recipe_key=key)
                                    for s in _batch_specs()])
        bids = {r.request_id for r in bs}
    # the FIFO baseline submits the trickle untagged — no class priority
    slo = "interactive" if with_gateway or not with_batch else "batch"
    irs = app.submit_stream(ex, [dict(s, recipe_key=key)
                                 for s in _interactive_specs(slo)])
    iids = {r.request_id for r in irs}
    fac.reconcile(N_WORKERS)
    ex.run(until=UNTIL)
    assert sched.done, f"{name}: run hit the {UNTIL:.0f}s safety net"
    return sched, gw, iids, bids


def _e2e_window(sched, ids):
    """Steady-state e2e latencies of served requests in ``ids``."""
    return [r.t_end - r.t_arrival for r in sched.records
            if r.request_id in ids and r.outcome == "done"
            and r.t_arrival >= MEASURE_FROM]


def _batch_units_done(sched, bids):
    return sum(r.n_units for r in sched.records
               if r.request_id in bids and r.outcome == "done")


def _assert_no_sim_leaks(sched, gw):
    assert not sched.running, f"requests stuck in running: {sched.running}"
    assert all(not lane for lane in sched.lanes.values()), "non-empty lane"
    for w in sched.workers.values():
        for lib in w.libraries.values():
            assert not lib.batch, \
                f"slot leak: {w.worker_id} still holds {set(lib.batch)}"
    if gw is not None:
        assert not gw.pending_overflow, "requests parked in overflow"
    kv = sched.plane.kv_summary()
    assert kv["spill_events"] == sched.preemptions, \
        f"spill meter {kv['spill_events']} != preemptions {sched.preemptions}"
    assert kv["resume_events"] == kv["spill_events"], \
        f"{kv['spill_events']} spills but {kv['resume_events']} resumes: " \
        "a victim never returned"


def sim_section(smoke: bool):
    runs = {
        "unloaded": _run("unloaded", with_batch=False, with_gateway=False),
        "baseline": _run("baseline", with_batch=True, with_gateway=False),
        "gateway": _run("gateway", with_batch=True, with_gateway=True),
    }
    rep = Report(
        f"serving gateway: interactive p95 under {BATCH_REQS}-request "
        f"batch overload ({N_WORKERS}xA10, {BURST_N}-request bursts)",
        ["run", "int p95 s", "int done", "int t/o", "batch units",
         "preempt", "makespan s"])
    p95 = {}
    for name, (sched, gw, iids, bids) in runs.items():
        xs = _e2e_window(sched, iids)
        p95[name] = percentile(xs, 95)
        irec = [r for r in sched.records if r.request_id in iids]
        n_to = sum(r.outcome == "timed_out" for r in irec)
        n_done = sum(r.outcome == "done" for r in irec)
        rep.add(name, f"{p95[name]:.2f}", n_done, n_to,
                _batch_units_done(sched, bids), sched.preemptions,
                f"{sched.makespan():.0f}")
    rep.print()

    sched_gw, gw, iids_gw, bids_gw = runs["gateway"]
    sched_fifo, _, _, bids_fifo = runs["baseline"]
    ratio = p95["gateway"] / p95["unloaded"]
    print(f"interactive p95: unloaded {p95['unloaded']:.2f}s, "
          f"FIFO {p95['baseline']:.2f}s, gateway {p95['gateway']:.2f}s "
          f"({ratio:.2f}x unloaded) — {sched_gw.preemptions} preemption(s)")
    _assert_no_sim_leaks(sched_gw, gw)
    for name, (sched, g, _, _) in runs.items():
        if name != "gateway":
            _assert_no_sim_leaks(sched, g)
    if smoke:
        assert sched_gw.preemptions > 0, \
            "overload never triggered a preemption — the deadline path " \
            "is dead code in this scenario"
        assert ratio <= 1.2, \
            f"gateway interactive p95 is {ratio:.2f}x unloaded (> 1.2x): " \
            "the SLO class did not hold under overload"
        assert p95["baseline"] > 3 * p95["unloaded"], \
            "FIFO baseline was not actually overloaded — the comparison " \
            "is vacuous"
        done_gw = _batch_units_done(sched_gw, bids_gw)
        done_fifo = _batch_units_done(sched_fifo, bids_fifo)
        assert done_gw == done_fifo, \
            f"gateway completed {done_gw} batch units vs FIFO " \
            f"{done_fifo}: preemption lost work"
        rec_gw = [r for r in sched_gw.records if r.request_id in iids_gw]
        assert all(r.outcome in ("done", "timed_out") for r in rec_gw)
        print("smoke OK: interactive p95 held <= 1.2x unloaded at equal "
              "batch work, zero slot leaks")


# -- live suspend/resume ----------------------------------------------------
def _token_exactness(cfg, params):
    """Suspend a victim mid-decode, step the others, resume: the victim's
    tokens must be bit-exact vs an uninterrupted run.  Returns the
    suspended byte count for the caller to report."""
    import numpy as np
    from repro.inference import StreamingDecoder

    rng = np.random.default_rng(7)
    prompts = {r: list(rng.integers(4, cfg.vocab_size, 12 + 3 * r))
               for r in range(3)}
    kw = dict(max_len=64, page_size=8)

    def fresh():
        dec = StreamingDecoder(cfg, params, None, None, **kw)
        for r, p in prompts.items():
            dec.ensure_tokens(r, list(p))
        return dec

    def collect(dec, rids, steps, outs):
        for _ in range(steps):
            for r, t in dec.step(rids).items():
                outs.setdefault(r, []).append(t)

    victim = 0
    dec, outs = fresh(), {}
    collect(dec, [0, 1, 2], 4, outs)
    nb = dec.suspend(victim)
    assert nb > 0, "suspend moved zero bytes"
    assert victim not in dec.pool.slot_of, "victim kept its slot"
    collect(dec, [1, 2], 3, outs)            # others decode while spilled
    dec.resume(victim)
    collect(dec, [0, 1, 2], 6, outs)
    for r in range(3):
        dec.finish(r)

    ref, routs = fresh(), {}
    collect(ref, [0, 1, 2], 10, routs)
    for r in range(3):
        ref.finish(r)

    assert outs[victim] == routs[victim], \
        f"resumed token stream diverged from the uninterrupted " \
        f"reference ({outs[victim]} vs {routs[victim]})"
    assert not dec._suspended, "suspended snapshot leaked"
    assert dec.pool.free == dec.pool.capacity, \
        f"slot leak ({dec.pool.free}/{dec.pool.capacity} free)"
    assert dec.pages.in_use == 0, f"{dec.pages.in_use} page(s) leaked"
    assert dec.kv_suspend_bytes_total == dec.kv_resume_bytes_total > 0
    return dec.kv_suspend_bytes_total


def _retention_check():
    """PagePool prefix retention: park at refcount zero, revive on hit,
    reclaim LRU-first only under allocation pressure."""
    from repro.inference.streaming import PagePool
    pool = PagePool(4, retained_cap=2)       # pages 1..3 (0 is TRASH)
    evicted = []
    pool.on_evict_retained = evicted.append
    p0, p1 = pool.alloc(), pool.alloc()
    assert pool.decref(p0) is False and pool.retained_count == 1
    pool.incref(p0)                          # prefix hit revives the park
    assert pool.retained_count == 0 and pool.refcount(p0) == 1
    assert pool.decref(p0) is False and pool.decref(p1) is False
    assert pool.retained_count == 2 and pool.in_use == 0
    pool.alloc()                             # last truly-free page
    got = pool.alloc()                       # pressure: LRU park reclaimed
    assert got == p0 and evicted == [p0], \
        f"expected LRU-first reclaim of {p0}, got {got} (evicted {evicted})"
    assert pool.retained_count == 1
    print("retention OK: park at zero, revive on hit, LRU reclaim under "
          "pressure only")


def live_section(smoke: bool):
    import jax
    from repro.configs import get_smoke_config
    from repro.models import model as M

    print("\n== live suspend/resume: token exactness + accounting ==")
    _retention_check()
    cfg = get_smoke_config("smollm2-1.7b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    nbytes = _token_exactness(cfg, params)
    print(f"victim resumed bit-exact after mid-decode suspension "
          f"({nbytes} KV bytes spilled+restored, zero slot/page leaks)")
    if smoke:
        print("smoke OK: suspend/resume token-exact")


def main(smoke: bool = False) -> int:
    sim_section(smoke)
    live_section(smoke)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: fail on p95 regression, lost batch "
                         "work, token divergence, or accounting leaks")
    args = ap.parse_args()
    sys.exit(main(smoke=args.smoke))

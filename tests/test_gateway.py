"""Serving gateway: SLO classes, bounded queues, deadline preemption.

Covers the gateway contract end to end: admission bounds (reject vs
overflow-queue), re-admission bypass, deadline stamping and DES-event
expiry, terminal-outcome mutual exclusion, deadline-driven batch
preemption with KV suspend/resume on both executors, and the
outcome-aware latency summaries.

The Hypothesis section property-checks the two DES-wide invariants
(per-class queue bounds hold at EVERY event; terminal outcomes are
recorded exactly once and are mutually exclusive) plus work conservation
under preemption.  Token bit-exactness across a suspend/resume cycle is
checked against the real :class:`StreamingDecoder` (deterministically
parametrized — a real model per Hypothesis example would be
prohibitive; the DES property covers the schedule space instead).
"""
import dataclasses

import pytest

from repro.core import model_context_recipe
from repro.cluster import (Application, ClassPolicy, GPU_CATALOG, Gateway,
                           REJECTED, Request, Scheduler, TIMED_OUT, Worker,
                           class_latency_summary, latency_summary, make_sim)
from repro.cluster.scheduler import RequestRecord
from repro.configs import get_config

CFG = get_config("smollm2-1.7b")
AP = CFG.n_active_params()
A10 = GPU_CATALOG["NVIDIA A10"]

# ~2 decode slots per 24 GB A10 (deterministic slot budget)
RECIPE2 = dataclasses.replace(model_context_recipe(CFG, include_compile=False),
                              slot_bytes=10_000_000_000)


def mk_sim(n_workers=1, *, interactive=None, batch=None, with_gateway=True):
    sched, ex, fac = make_sim(devices=[A10] * max(n_workers, 1),
                              workers_per_zone=max(n_workers, 1))
    app = Application(sched)
    key = app.register(RECIPE2, active_params=AP)
    gw = Gateway(sched, interactive=interactive, batch=batch) \
        if with_gateway else None
    if n_workers:
        fac.reconcile(n_workers)
    return sched, ex, fac, app, key, gw


class TestAdmission:
    def test_reject_overflow_is_terminal(self):
        sched, ex, _, app, key, gw = mk_sim(0, interactive=ClassPolicy(
            max_queue=2, overflow="reject", deadline_s=60.0))
        reqs = [app.submit(key, decode_steps=1, slo="interactive")
                for _ in range(3)]
        assert gw.queued_fresh(key, "interactive") == 2
        assert gw.rejected["interactive"] == 1
        rec = [r for r in sched.records
               if r.request_id == reqs[2].request_id]
        assert len(rec) == 1 and rec[0].outcome == REJECTED
        assert rec[0].slo == "interactive"
        # a rejected request is terminal: never in a lane, never runs
        assert all(r is not reqs[2] for lane in sched.lanes.values()
                   for r in lane)

    def test_queue_overflow_parks_and_never_exceeds_bound(self):
        sched, ex, fac, app, key, gw = mk_sim(1, batch=ClassPolicy(
            max_queue=2, overflow="queue"))
        for _ in range(5):
            app.submit(key, decode_steps=2, slo="batch")
        assert gw.queued_fresh(key, "batch") == 2
        assert gw.pending_overflow == 3
        assert not sched.done, "parked requests must hold the run open"
        ex.run()
        assert sched.done and gw.pending_overflow == 0
        assert sched.completed_inferences == 10
        assert all(r.outcome == "done" for r in sched.records)

    def test_readmission_bypasses_bound(self):
        sched, _, _, app, key, gw = mk_sim(0, batch=ClassPolicy(
            max_queue=1, overflow="queue"))
        app.submit(key, decode_steps=2, slo="batch")
        veteran = app.make_request(key, decode_steps=2, slo="batch")
        veteran.attempts = 1                   # evicted elsewhere, requeued
        sched.ingress(veteran)
        assert gw.pending_overflow == 0, "re-admission must not park"
        assert sum(len(l) for l in sched.lanes.values()) == 2

    def test_deadline_stamped_relative_to_arrival(self):
        sched, _, _, app, key, _ = mk_sim(0, interactive=ClassPolicy(
            max_queue=8, overflow="reject", deadline_s=30.0))
        r = app.submit(key, decode_steps=1, slo="interactive", arrival_s=5.0)
        assert r.deadline_s == 35.0
        explicit = app.submit(key, decode_steps=1, slo="interactive",
                              deadline_s=12.0)
        assert explicit.deadline_s == 12.0, "explicit deadline kept"

    def test_unknown_slo_rejected(self):
        sched, _, _, app, key, _ = mk_sim(0)
        with pytest.raises(ValueError, match="SLO class"):
            app.submit(key, decode_steps=1, slo="bulk")

    def test_interactive_lane_prefix_invariant(self):
        sched, _, _, app, key, _ = mk_sim(0)
        for slo in ("batch", "interactive", "batch", "interactive"):
            app.submit(key, decode_steps=1, slo=slo)
        lane = list(sched.lanes[key])
        slos = [r.slo for r in lane]
        assert slos == ["interactive", "interactive", "batch", "batch"]
        # FIFO within each class
        assert [r.request_id for r in lane if r.slo == "interactive"] == \
            sorted(r.request_id for r in lane if r.slo == "interactive")


class TestDeadline:
    def test_expiry_fires_as_des_event_on_idle_pool(self):
        """A queued deadline must fire even when nothing else happens —
        the sim arms a timer for it (no busy-wait, no hang)."""
        sched, ex, _, app, key, gw = mk_sim(0, interactive=ClassPolicy(
            max_queue=8, overflow="reject", deadline_s=5.0))
        app.submit_stream(ex, [dict(recipe_key=key, decode_steps=1,
                                    arrival_s=1.0, slo="interactive")])
        ex.run(until=100.0)
        assert sched.done
        assert ex.loop.now < 10.0, "loop ran to the safety net, not the " \
            "deadline event"
        assert gw.timed_out["interactive"] == 1
        (rec,) = sched.records
        assert rec.outcome == TIMED_OUT and rec.t_end == pytest.approx(
            6.0, abs=0.1)

    def test_overflowed_requests_also_expire(self):
        sched, ex, _, app, key, gw = mk_sim(0, interactive=ClassPolicy(
            max_queue=1, overflow="queue", deadline_s=4.0))
        for _ in range(3):
            app.submit(key, decode_steps=1, slo="interactive")
        assert gw.pending_overflow == 2
        gw.expire(10.0)
        assert gw.pending_overflow == 0
        assert gw.timed_out["interactive"] == 3
        assert {r.outcome for r in sched.records} == {TIMED_OUT}

    def test_terminal_outcome_recorded_exactly_once(self):
        sched = Scheduler()
        key = sched.register_context(RECIPE2)
        req = Request(key, decode_steps=1)
        sched.record_terminal(req, REJECTED, 0.0)
        with pytest.raises(AssertionError):
            sched.record_terminal(req, TIMED_OUT, 1.0)
        assert [r.outcome for r in sched.records] == [REJECTED]


def run_preemption_scenario(*, n_workers=1, batch_steps=60, int_steps=4,
                            int_arrival=30.0, deadline=8.0):
    """Fill the pool with long batch decodes, then land one deadline'd
    interactive request that can only be served by preempting."""
    sched, ex, fac, app, key, gw = mk_sim(
        n_workers, interactive=ClassPolicy(
            max_queue=8, overflow="reject", deadline_s=deadline,
            preempt_slack_s=deadline))
    n_batch = 2 * n_workers                     # 2 slots per worker
    app.submit_stream(ex, [dict(recipe_key=key, decode_steps=batch_steps,
                                arrival_s=0.0, slo="batch")
                           for _ in range(n_batch)])
    app.submit_stream(ex, [dict(recipe_key=key, decode_steps=int_steps,
                                arrival_s=int_arrival, slo="interactive")])
    fac.reconcile(n_workers)
    ex.run(until=2_000.0)
    return sched, gw, app


class TestPreemption:
    def test_deadline_preempts_batch_and_victim_resumes(self):
        sched, gw, app = run_preemption_scenario()
        assert sched.done
        assert sched.preemptions == 1
        by_slo = {}
        for r in sched.records:
            by_slo.setdefault(r.slo, []).append(r)
        (irec,) = by_slo["interactive"]
        assert irec.outcome == "done"
        # deadlines bound QUEUE time: the interactive request started
        # decoding before its (absolute) deadline
        assert irec.t_first_step <= 30.0 + 8.0
        victims = [r for r in by_slo["batch"] if r.preemptions > 0]
        assert len(victims) == 1 and victims[0].outcome == "done"
        # work conservation: nothing lost across the suspend/resume cycle
        assert sched.completed_inferences == 2 * 60 + 4
        kv = sched.plane.kv_summary()
        assert kv["spill_events"] == 1 and kv["resume_events"] == 1
        assert kv["spilled_bytes"] == kv["resumed_bytes"] > 0
        # no slot leaks
        for w in sched.workers.values():
            for lib in w.libraries.values():
                assert not lib.batch

    def test_no_preemption_without_gateway(self):
        sched, ex, fac, app, key, _ = mk_sim(1, with_gateway=False)
        app.submit_stream(ex, [dict(recipe_key=key, decode_steps=60,
                                    arrival_s=0.0, slo="batch")
                               for _ in range(2)])
        app.submit_stream(ex, [dict(recipe_key=key, decode_steps=4,
                                    arrival_s=30.0, slo="interactive",
                                    deadline_s=38.0)])
        fac.reconcile(1)
        ex.run(until=2_000.0)
        assert sched.done and sched.preemptions == 0

    def test_victim_redispatches_fresh_when_worker_lost(self):
        """Eviction of the suspended-on worker voids the KV snapshot:
        the victim must restart from step 0 elsewhere, not resume."""
        sched, ex, fac, app, key, gw = mk_sim(2, interactive=ClassPolicy(
            max_queue=8, overflow="reject", deadline_s=8.0,
            preempt_slack_s=8.0))
        app.submit_stream(ex, [dict(recipe_key=key, decode_steps=400,
                                    arrival_s=0.0, slo="batch")
                               for _ in range(4)])
        app.submit_stream(ex, [dict(recipe_key=key, decode_steps=4,
                                    arrival_s=30.0, slo="interactive")])
        fac.reconcile(2)
        ex.pump()
        # pause right after the preemption, before the victim can resume
        ex.loop.run(until=30.5)
        assert sched.preemptions == 1
        victim = next(r for lane in sched.lanes.values() for r in lane
                      if r.suspended)
        wid = victim.suspended_on
        assert wid is not None
        sched.on_evict(wid, now=ex.loop.now)
        fac.reconcile(2)                       # replacement joins
        ex.run(until=5_000.0)
        assert sched.done
        assert not victim.suspended and victim.suspended_on is None, \
            "stale suspension survived the worker loss"
        vrec = [r for r in sched.records
                if r.request_id == victim.request_id]
        assert len(vrec) == 1 and vrec[0].outcome == "done"


class TestOutcomeAwareSummaries:
    @staticmethod
    def _rec(rid, outcome="done", preemptions=0, slo="batch", t_end=10.0):
        return RequestRecord(
            request_id=rid, worker_id="w", device="d", t_arrival=0.0,
            t_start=1.0, t_first_step=2.0, t_end=t_end, n_units=4,
            warm=True, attempts=0, outcome=outcome, slo=slo,
            preemptions=preemptions)

    def test_terminal_and_preempted_records_do_not_pollute_percentiles(self):
        recs = [self._rec(1, t_end=10.0),
                self._rec(2, outcome=REJECTED, t_end=0.01),
                self._rec(3, outcome=TIMED_OUT, t_end=0.5),
                self._rec(4, preemptions=2, t_end=500.0)]
        s = latency_summary(recs)
        assert s["n"] == 4 and s["n_done"] == 2
        assert s["n_rejected"] == 1 and s["n_timed_out"] == 1
        assert s["n_preempted"] == 1
        # only the cleanly served record feeds the distribution: neither
        # the instant refusals nor the suspension-smeared e2e leak in
        assert s["e2e_p50_s"] == s["e2e_p95_s"] == 10.0

    def test_class_split(self):
        recs = [self._rec(1, slo="interactive", t_end=2.0),
                self._rec(2, slo="batch", t_end=90.0)]
        s = class_latency_summary(recs)
        assert set(s) == {"interactive", "batch"}
        assert s["interactive"]["e2e_p50_s"] == 2.0
        assert s["batch"]["e2e_p50_s"] == 90.0


class TestPagePoolRetention:
    def test_park_revive_and_pressure_reclaim(self):
        from repro.inference.streaming import PagePool
        pool = PagePool(5, retained_cap=2)
        dropped = []
        pool.on_evict_retained = dropped.append
        a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
        assert pool.decref(a) is False, "parked, not freed"
        assert pool.retained_count == 1 and not dropped
        pool.incref(a)                          # prefix hit revives
        assert pool.retained_count == 0 and pool.refcount(a) == 1
        for p in (a, b, c):
            assert pool.decref(p) is False
        # the park overflowed its cap: oldest page actually freed
        assert pool.retained_count == 2 and dropped == [a]
        pool.alloc()                            # free list still preferred
        assert pool.retained_count == 2
        pool.alloc()
        got = pool.alloc()                      # pressure: LRU reclaim
        assert got == b and dropped == [a, b]

    def test_cap_zero_frees_immediately(self):
        from repro.inference.streaming import PagePool
        pool = PagePool(3)
        p = pool.alloc()
        assert pool.decref(p) is True
        assert pool.retained_count == 0 and pool.free == 2


# ---------------------------------------------------------------------------
# Live suspend/resume: token bit-exactness (real decoder, deterministic)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_setup():
    import jax
    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = get_smoke_config("smollm2-1.7b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def test_suspend_resume_tokens_bit_exact(live_setup):
    import numpy as np
    from repro.inference import StreamingDecoder
    cfg, params = live_setup
    rng = np.random.default_rng(3)
    prompts = {r: list(rng.integers(4, cfg.vocab_size, 10 + 2 * r))
               for r in range(3)}
    kw = dict(max_len=48, page_size=8)

    def decode(suspend_at):
        dec = StreamingDecoder(cfg, params, None, None, **kw)
        for r, p in prompts.items():
            dec.ensure_tokens(r, list(p))
        outs = {}
        done = 0
        while done < 8:
            if suspend_at is not None and done == suspend_at:
                assert dec.suspend(0) > 0
                for _ in range(2):              # others decode meanwhile
                    dec.step([1, 2])
                dec.resume(0)
            for r, t in dec.step([0, 1, 2]).items():
                outs.setdefault(r, []).append(t)
            done += 1
        for r in prompts:
            dec.finish(r)
        assert dec.pool.free == dec.pool.capacity, "slot leak"
        assert dec.pages.in_use == 0, "page leak"
        assert not dec._suspended
        return outs[0]

    reference = decode(None)
    for point in (1, 5):
        assert decode(point) == reference, \
            f"tokens diverged after suspend at step {point}"

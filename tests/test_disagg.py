"""Prefill/decode disaggregation: per-phase device model, phase-tagged
routing with the ship-vs-local rule, the KV_SHIP lifecycle on the
context plane, phase-split latency records, the preemption-rate warm
pool signal, and shipped-KV token exactness on the live decoder.
"""
import dataclasses

import pytest

from repro.core import (ClusterView, LinkBudget, OpKind, WarmPoolPolicy,
                        model_context_recipe)
from repro.cluster import (Application, DECODE, GPU_CATALOG, Gateway,
                           LiveExecutor, PREFILL, Scheduler, Worker,
                           format_latency, latency_summary, make_sim,
                           pool_rate)
from repro.cluster.hardware import (DeviceModel, PREFILL_MFU,
                                    PREFILL_TOKENS_PER_UNIT)
from repro.configs import get_config

CFG = get_config("smollm2-1.7b")
RECIPE = model_context_recipe(CFG, include_compile=False)
AP = CFG.n_active_params()
A10 = GPU_CATALOG["NVIDIA A10"]
ADA = GPU_CATALOG["NVIDIA RTX 6000 Ada Generation"]
H100 = GPU_CATALOG["NVIDIA H100 80GB HBM3"]
TITAN = GPU_CATALOG["NVIDIA TITAN X (Pascal)"]

# compute-rich but HBM-poor vs the reverse: a rig where shipping the KV
# after prefill strictly beats decoding in place
PREFILL_RIG = DeviceModel("prefill-rig", 2024, 1, 1.0, 24, 500e6, 8e9,
                          tflops=500.0)
DECODE_RIG = DeviceModel("decode-rig", 2024, 1, 0.08, 80, 500e6, 8e9,
                         tflops=5.0)


def _run_disagg_sim(devices, n_reqs, *, disaggregate=True, prompt_units=4,
                    decode_steps=32, workers_per_zone=4, arrival_every=0.25):
    sched, ex, fac = make_sim(devices=devices,
                              workers_per_zone=workers_per_zone,
                              disaggregate=disaggregate)
    app = Application(sched)
    key = app.register(RECIPE, active_params=AP)
    app.submit_stream(ex, [dict(recipe_key=key, prompt_units=prompt_units,
                                decode_steps=decode_steps,
                                arrival_s=i * arrival_every)
                           for i in range(n_reqs)])
    fac.reconcile(len(devices))
    ex.run(until=20_000.0)
    assert sched.done
    return sched


def assert_kv_balanced(sched):
    assert sched.plane.planned.as_dict() == sched.plane.moved.as_dict()
    assert sched.plane.inflight_ops == 0
    kv = sched.plane.kv_summary()
    assert sum(sched.plane.kv_shipped.values()) == kv["shipped_bytes"]


# ---------------------------------------------------------------------------
# DeviceModel: the two phases rank devices differently
# ---------------------------------------------------------------------------

class TestPhaseModel:
    def test_prefill_is_flop_bound(self):
        flops = 2.0 * AP * PREFILL_TOKENS_PER_UNIT
        assert H100.prefill_time(AP, 1) == pytest.approx(
            flops / (H100.tflops * 1e12 * PREFILL_MFU))
        assert H100.prefill_time(AP, 3) == pytest.approx(
            3 * H100.prefill_time(AP, 1))

    def test_phase_spreads_diverge(self):
        """The disaggregation opportunity: matmul throughput spreads far
        wider across the catalog than HBM-bound decode speed."""
        decode_spread = TITAN.infer_time(AP) / H100.infer_time(AP)
        prefill_spread = TITAN.prefill_time(AP, 1) / H100.prefill_time(AP, 1)
        assert prefill_spread > 5 * decode_spread

    def test_uncatalogued_tflops_falls_back_to_balanced(self):
        legacy = dataclasses.replace(A10, tflops=0.0)
        assert legacy.prefill_time(AP, 5) == pytest.approx(
            5 * legacy.infer_time(AP))

    def test_pool_rate_phases(self):
        pool = [ADA, A10, TITAN]
        legacy = pool_rate(pool, AP)
        assert legacy == pytest.approx(
            sum(1.0 / d.infer_time(AP) for d in pool))
        prefill = pool_rate(pool, AP, phase="prefill")
        decode = pool_rate(pool, AP, phase="decode")
        assert prefill == pytest.approx(
            sum(1.0 / d.prefill_time(AP, 1) for d in pool))
        # every device counts toward BOTH phase capacities
        assert decode == pytest.approx(
            sum(1.0 / d.step_time(AP, 1) for d in pool))
        with pytest.raises(ValueError):
            pool_rate(pool, AP, phase="training")


# ---------------------------------------------------------------------------
# Phase tagging at submit
# ---------------------------------------------------------------------------

class TestPhaseTagging:
    def _mk(self, disaggregate):
        sched = Scheduler(disaggregate=disaggregate)
        app = Application(sched)
        key = app.register(RECIPE, active_params=AP)
        return sched, app, key

    def test_split_candidate_is_tagged_prefill(self):
        sched, app, key = self._mk(True)
        r = app.submit(key, prompt_units=3, decode_steps=8, payload=0)
        assert r.phase == PREFILL

    def test_untagged_without_opt_in_or_prompt(self):
        sched, app, key = self._mk(False)
        assert app.submit(key, prompt_units=3, decode_steps=8,
                          payload=0).phase is None
        sched, app, key = self._mk(True)
        assert app.submit(key, decode_steps=8, payload=0).phase is None


# ---------------------------------------------------------------------------
# KV_SHIP lifecycle on the plane
# ---------------------------------------------------------------------------

class TestShipLifecycle:
    def _plane(self):
        sched = Scheduler()
        sched.register_context(RECIPE)
        return sched.plane

    def _op(self, plane, nbytes=1000, dst_zone="z1"):
        return plane.kv_ship_op(RECIPE.key, "w0", "w1", nbytes,
                                src_zone="z0", dst_zone=dst_zone)

    def test_commit_then_complete_balances(self):
        plane = self._plane()
        op = self._op(plane)
        plane.commit_kv_ship(7, op)
        assert plane.inflight_ops == 1
        plane.kv_ship_completed(7)
        assert plane.planned.as_dict() == plane.moved.as_dict()
        assert plane.kv_shipped == {"z1": 1000}
        assert plane.kv_summary()["ship_events"] == 1
        assert plane.inflight_ops == 0

    def test_complete_is_stale_safe(self):
        plane = self._plane()
        plane.commit_kv_ship(7, self._op(plane))
        plane.kv_ship_completed(7)
        plane.kv_ship_completed(7)          # late DES timer: no-op
        assert plane.kv_summary()["ship_events"] == 1
        assert plane.planned.as_dict() == plane.moved.as_dict()

    def test_abort_refunds_and_is_idempotent(self):
        plane = self._plane()
        plane.commit_kv_ship(7, self._op(plane))
        plane.kv_ship_aborted(7)
        plane.kv_ship_aborted(7)
        assert plane.inflight_ops == 0
        assert plane.kv_summary()["ship_events"] == 0
        # full refund: the planned meter nets back to zero everywhere
        assert all(v == 0 for row in plane.planned.as_dict().values()
                   for v in row.values())

    def test_drop_worker_aborts_touching_ships(self):
        plane = self._plane()
        plane.commit_kv_ship(1, self._op(plane))                # src dies
        plane.commit_kv_ship(2, plane.kv_ship_op(
            RECIPE.key, "w2", "w0", 500, src_zone="z1", dst_zone="z0"))
        plane.commit_kv_ship(3, plane.kv_ship_op(
            RECIPE.key, "w2", "w3", 500, src_zone="z1", dst_zone="z1"))
        plane.drop_worker("w0")
        assert sorted(plane._inflight_ships) == [3]
        plane.kv_ship_completed(3)
        assert plane.planned.as_dict() == plane.moved.as_dict()

    def test_ship_admission_respects_link_budget(self):
        sched = Scheduler(link_budget=LinkBudget(
            cross_bytes_per_window=100, window_s=10.0))
        sched.register_context(RECIPE)
        plane = sched.plane
        small = self._op(plane, nbytes=80)
        big = self._op(plane, nbytes=200)
        assert plane.ship_admits(small, 0.0)
        assert not plane.ship_admits(big, 0.0)
        plane.commit_kv_ship(1, small, 0.0)
        assert not plane.ship_admits(small, 1.0)    # window now full
        assert plane.ship_admits(small, 60.0)       # window slid past


# ---------------------------------------------------------------------------
# Routing: ship-vs-local in the DES
# ---------------------------------------------------------------------------

class TestShipVsLocal:
    def test_homogeneous_pool_takes_the_fast_path(self):
        """Identical devices: shipping only adds cost, so every decode
        stays on its prefill worker."""
        sched = _run_disagg_sim([A10] * 2, 8, workers_per_zone=2,
                                decode_steps=8)
        assert sched.kv_ships == 0
        assert sched.local_decodes == 8
        assert sched.prefills_done == 8
        assert_kv_balanced(sched)

    def test_heterogeneous_pool_ships(self):
        """Mixed pool under load: once the compute-rich workers' decode
        slots fill, freshly prefilled KV ships to the memory-side pool
        instead of queueing behind the fast prefill engines."""
        sched = _run_disagg_sim([ADA] * 2 + [A10] * 6, 40)
        assert sched.kv_ships > 0
        assert sched.prefills_done == 40
        assert sched.plane.kv_summary()["shipped_bytes"] > 0
        shipped = [r for r in sched.records
                   if r.outcome == "done" and r.ship_s > 0]
        assert len(shipped) == sched.kv_ships
        assert_kv_balanced(sched)

    def test_disaggregation_completes_equal_work_no_slower(self):
        pool = [ADA] * 2 + [A10] * 6
        col = _run_disagg_sim(pool, 40, disaggregate=False)
        dis = _run_disagg_sim(pool, 40, disaggregate=True)

        def units(s):
            return sum(r.n_units for r in s.records if r.outcome == "done")
        assert units(dis) == units(col) > 0
        assert dis.kv_ships > 0
        assert dis.makespan() <= col.makespan() * 1.01
        assert_kv_balanced(dis)
        assert_kv_balanced(col)

    def test_legacy_run_is_untouched(self):
        """disaggregate=False never phase-splits, ships, or prefills."""
        sched = _run_disagg_sim([A10] * 4, 12, disaggregate=False)
        assert sched.kv_ships == sched.local_decodes == 0
        assert sched.prefills_done == 0
        assert all(r.prefill_s == 0.0 for r in sched.records)
        assert_kv_balanced(sched)


# ---------------------------------------------------------------------------
# Per-phase latency records
# ---------------------------------------------------------------------------

class TestPhaseLatency:
    def test_records_split_by_phase(self):
        sched = _run_disagg_sim([PREFILL_RIG, DECODE_RIG], 8,
                                workers_per_zone=2, decode_steps=8,
                                arrival_every=0.0)
        done = [r for r in sched.records if r.outcome == "done"]
        assert all(r.prefill_s > 0 for r in done)
        shipped = [r for r in done if r.ship_s > 0]
        assert len(shipped) == sched.kv_ships
        for r in done:
            assert r.decode_s == pytest.approx(
                max(0.0, (r.t_end - r.t_start) - r.ship_s))

    def test_latency_summary_reports_phases(self):
        sched = _run_disagg_sim([PREFILL_RIG, DECODE_RIG], 8,
                                workers_per_zone=2, decode_steps=8,
                                arrival_every=0.0)
        summ = latency_summary(sched.records)
        assert summ["n_phased"] == 8
        assert summ["n_shipped"] == sched.kv_ships
        for name in ("prefill", "ship", "decode"):
            assert f"{name}_p50_s" in summ
        assert "[phases]" in format_latency(summ)

    def test_phase_keys_absent_without_disaggregation(self):
        sched = _run_disagg_sim([A10] * 2, 6, disaggregate=False,
                                workers_per_zone=2, decode_steps=8)
        summ = latency_summary(sched.records)
        assert "n_phased" not in summ and "prefill_p50_s" not in summ
        assert "[phases]" not in format_latency(summ)


# ---------------------------------------------------------------------------
# Preemption-rate warm-pool signal (satellite)
# ---------------------------------------------------------------------------

class TestPreemptHorizon:
    def _view(self, sched, key, rate):
        return ClusterView(workers=sched.workers, registry=sched.registry,
                           demand={key: 1}, preempt_rate={key: rate})

    def test_preempt_rate_inflates_replica_demand(self):
        sched = Scheduler()
        key = sched.register_context(RECIPE)
        for _ in range(8):
            sched.add_worker(Worker(A10))
        reactive = WarmPoolPolicy(tasks_per_replica=1, max_fraction=1.0)
        stormy = dataclasses.replace(reactive, preempt_horizon_s=10.0)
        view = self._view(sched, key, rate=0.5)
        # 1 queued + 0.5/s * 10s horizon = 6 tasks of demand
        assert reactive.intents(view)[0].n == 1
        assert stormy.intents(view)[0].n == 6

    def test_scheduler_tracks_preemption_ewma(self):
        sched = Scheduler()
        key = sched.register_context(RECIPE)
        for t in (10.0, 11.0, 12.0):
            sched._note_event(sched._preempts, key, t)
        assert sched.view(12.0).preempt_rate[key] > 0
        assert sched.view(12.0).preempt_rate.get("other") is None


# ---------------------------------------------------------------------------
# Gateway: banked progress never times out at the edge (satellite)
# ---------------------------------------------------------------------------

class TestExpirableProgress:
    def test_decode_phase_requeue_keeps_its_slot(self):
        sched = Scheduler()
        gw = Gateway(sched)
        app = Application(sched)
        key = app.register(RECIPE, active_params=AP)
        fresh = app.make_request(key, decode_steps=4, payload=0,
                                 slo="interactive", deadline_s=5.0)
        banked = app.make_request(key, decode_steps=4, payload=1,
                                  slo="interactive", deadline_s=5.0)
        banked.steps_done = 2           # mid-service: prefill KV is banked
        sched.submit(fresh)
        sched.submit(banked)
        assert gw.next_deadline() == 5.0
        expired = gw.expire(10.0)
        assert [r.request_id for r in expired] == [fresh.request_id]
        assert banked in sched.lanes[key]
        # the deadline timer must never re-arm on the unexpirable request
        assert gw.next_deadline() is None


# ---------------------------------------------------------------------------
# Live: shipped KV decodes token-exact
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_setup():
    from repro.configs import get_smoke_config
    from repro.data import generate_claims
    from repro.inference import build_context_recipe
    cfg = get_smoke_config("smollm2-1.7b")
    return (cfg, generate_claims(4, seed=2),
            build_context_recipe(cfg, "with_evidence"))


class TestLiveShippedKV:
    def _run(self, claims, recipe, *, disaggregate):
        from repro.inference import make_pff_step_fn
        sched = Scheduler(disaggregate=disaggregate)
        app = Application(sched)
        key = app.register(recipe)
        sched.add_worker(Worker(PREFILL_RIG))
        sched.add_worker(Worker(DECODE_RIG))
        for c in claims:
            app.submit(key, prompt_units=2, decode_steps=5, payload=c)
        ex = LiveExecutor(sched,
                          step_fns={key: make_pff_step_fn()})
        ex.run()
        return [ex.results[r.request_id] for r in app.requests], sched

    def test_shipped_decode_matches_colocated(self, live_setup):
        cfg, claims, recipe = live_setup
        base, _ = self._run(claims, recipe, disaggregate=False)
        dis, sched = self._run(claims, recipe, disaggregate=True)
        assert base == dis
        assert sched.kv_ships > 0
        assert sched.prefills_done == len(claims)
        assert all(len(t) == 7 for t in dis)     # 2 prefill + 5 decode
        assert sched.plane.kv_summary()["shipped_bytes"] > 0
        assert_kv_balanced(sched)

    def test_adopted_bytes_metered_apart_from_resume(self, live_setup):
        """A shipped snapshot adopts into the destination decoder's pool
        under its own counter, so preemption resume accounting stays
        exact."""
        cfg, claims, recipe = live_setup
        _, sched = self._run(claims, recipe, disaggregate=True)
        decs = [lib.context.payloads.get("_stream_decoder")
                for w in sched.workers.values()
                for lib in w.libraries.values()]
        decs = [d for d in decs if d is not None]
        assert sum(d.kv_adopt_bytes_total for d in decs) > 0
        # the same-worker fast path RESUMES its own suspended snapshot
        # (kv_resume_bytes_total); only shipped snapshots adopt
        resumed = sum(d.kv_resume_bytes_total for d in decs)
        assert (resumed > 0) == (sched.local_decodes > 0)

"""Live continuous batching: the persistent slot-pool decode is REAL.

The slot-cached streamed greedy decode must be token-exact against the
full-forward reference (both against a per-request full-forward loop and
against each other under membership churn), slot reuse must never leak a
freed tenant's K/V into the next one, the compiled-shape audit must stay
O(1) in decode steps, and the whole request-stream path must serve PfF
end-to-end through the LiveExecutor with per-request latency records —
feeding the measured per-slot cache bytes back into the recipe's slot
budget.
"""
import numpy as np
import pytest

from repro.cluster import Application, LiveExecutor, Scheduler, Worker
from repro.cluster.hardware import GPU_CATALOG
from repro.configs import get_smoke_config
from repro.data import accuracy, generate_claims
from repro.data.tokenizer import ByteTokenizer
from repro.inference import (MAX_NEW, StreamingDecoder, build_context_recipe,
                             make_pff_step_fn, stream_verdict)
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm2-1.7b")
    claims = generate_claims(10, seed=2)
    recipe = build_context_recipe(cfg, "with_evidence")
    payloads = {e.name: e.loader() for e in recipe.elements}
    return cfg, claims, recipe, payloads


class TestStreamingDecoder:
    def test_streamed_greedy_matches_reference(self, setup):
        """Batched-and-padded stepping == isolated full-forward greedy."""
        cfg, claims, _, payloads = setup
        eng = payloads["xla_executable"]
        ci = payloads["context_inputs"]
        dec = StreamingDecoder(eng.cfg, eng.params, ci["tokenizer"],
                               ci["template"])
        rids = list(range(4))
        for i in rids:
            dec.ensure(i, claims[i])
        streamed = {i: [] for i in rids}
        for _ in range(MAX_NEW):
            for i, t in dec.step(rids).items():
                streamed[i].append(t)
        for i in rids:
            toks = list(ci["tokenizer"].encode(
                ci["template"].render(claims[i]))[:96])
            ref = []
            for _ in range(MAX_NEW):
                logits = np.asarray(M.forward(
                    cfg, eng.params,
                    {"tokens": np.asarray([toks], np.int32)}))
                nxt = int(np.argmax(logits[0, len(toks) - 1]))
                toks.append(nxt)
                ref.append(nxt)
            assert streamed[i] == ref, f"request {i} diverged"

    def test_membership_churn_keeps_requests_exact(self, setup):
        """Requests leaving/joining between steps must not change the
        tokens of the ones that stay."""
        cfg, claims, _, payloads = setup
        eng = payloads["xla_executable"]
        ci = payloads["context_inputs"]
        mk = lambda: StreamingDecoder(eng.cfg, eng.params,
                                      ci["tokenizer"], ci["template"])
        solo, churn = mk(), mk()
        solo.ensure(0, claims[0])
        alone = []
        for _ in range(MAX_NEW):
            alone.append(solo.step([0])[0])
        churn.ensure(0, claims[0])
        churn.ensure(1, claims[1])
        churn.ensure(2, claims[2])
        got = []
        got.append(churn.step([0, 1, 2])[0])     # B=3 (padded to 4)
        got.append(churn.step([0, 1])[0])        # member 2 left
        churn.ensure(3, claims[3])
        for _ in range(MAX_NEW - 2):
            got.append(churn.step([0, 3])[0])    # member 3 joined
        assert got == alone

    def test_shape_buckets_bounded(self, setup):
        cfg, claims, _, payloads = setup
        eng = payloads["xla_executable"]
        ci = payloads["context_inputs"]
        dec = StreamingDecoder(eng.cfg, eng.params, ci["tokenizer"],
                               ci["template"])
        for i in range(6):
            dec.ensure(i, claims[i])
        for step in range(MAX_NEW):
            dec.step(list(range(6 if step < 4 else 3)))
        # the pool grows to 8 slots, where 6 and then 3 rows decode; the
        # 6 prompts (63-96 tokens) admit as 11 page-wide chunk rows, past
        # the capacity, so padded to a multiple of it
        assert sorted(dec._shapes) == [("decode", 8),
                                       ("prefill", 16, dec.page_size, 8)]
        assert dec.shape_buckets == 2


class TestSlotPoolDecoding:
    """The slot-cached path vs the full-forward reference path."""

    def _mk(self, payloads, **kw):
        eng = payloads["xla_executable"]
        ci = payloads["context_inputs"]
        return StreamingDecoder(eng.cfg, eng.params, ci["tokenizer"],
                                ci["template"], **kw)

    def _churn(self, dec, claims, budget, concurrent=3):
        """Admissions/finishes interleaved at every step: one admission per
        step while a slot is free, finish as soon as a request hits its
        budget.  Returns {rid: [tokens]}."""
        toks = {rid: [] for rid in budget}
        pending = sorted(budget, reverse=True)
        live = []
        while live or pending:
            if pending and len(live) < concurrent:
                rid = pending.pop()
                dec.ensure(rid, claims[rid])
                live.append(rid)
            for rid, t in dec.step(live).items():
                toks[rid].append(t)
            for rid in list(live):
                if len(toks[rid]) >= budget[rid]:
                    dec.finish(rid)
                    live.remove(rid)
        return toks

    @pytest.mark.parametrize("paged", [False, True])
    def test_churn_token_exact_and_slot_reuse_no_leak(self, setup, paged):
        """10 requests through a ≤4-slot pool, membership changing at
        every step: every slot is re-tenanted at least once, and the
        slot-cached tokens must equal the full-forward reference's —
        a freed slot's stale K/V leaking into its next tenant would
        diverge immediately.  Runs both KV layouts: contiguous per-slot
        rings and refcounted pages (the rendered claim prompts share the
        template preamble, so the paged run also exercises prefix reuse
        under churn)."""
        cfg, claims, _, payloads = setup
        slot = self._mk(payloads, paged=paged)
        full = self._mk(payloads, slot_cached=False)
        budget = {rid: 3 + (rid % 4) for rid in range(10)}
        got = self._churn(slot, claims, budget)
        ref = self._churn(full, claims, budget)
        assert got == ref
        assert slot.pool.capacity <= 4 < len(budget), \
            "pool must have re-tenanted freed slots"
        assert len(slot.pool) == 0 and slot.pool.free == slot.pool.capacity

    def test_recompile_audit_constant_in_steps(self, setup):
        """Stable membership: after the admission prefill and the first
        decode, EVERY further step reuses the same compiled shapes."""
        cfg, claims, _, payloads = setup
        dec = self._mk(payloads)
        for rid in range(3):
            dec.ensure(rid, claims[rid])
        rids = list(range(3))
        dec.step(rids)                                  # admission prefill
        dec.step(rids)                                  # first cached step
        buckets_after_two = dec.shape_buckets
        for _ in range(24):
            dec.step(rids)
        assert dec.shape_buckets == buckets_after_two
        # 3 prompts of 65-96 tokens: 6 chunk rows, padded to a multiple
        # of the 4-slot pool
        assert sorted(dec._shapes) == [("decode", 4),
                                       ("prefill", 8, dec.page_size, 4)]
        assert dec.shape_buckets == 2

    def test_b_max_presized_pool(self, setup):
        """A pool pre-sized to the library's slot budget never grows."""
        cfg, claims, _, payloads = setup
        dec = self._mk(payloads, b_max=4)
        for rid in range(4):
            dec.ensure(rid, claims[rid])
        dec.step(list(range(4)))
        assert dec.pool.capacity == 4
        assert dec.measured_slot_bytes > 0


class TestLiveStreamServing:
    def test_pff_request_stream_end_to_end(self, setup):
        cfg, claims, recipe, _ = setup
        sched = Scheduler()
        app = Application(sched)
        key = app.register(recipe)
        for _ in range(2):
            sched.add_worker(Worker(GPU_CATALOG["NVIDIA A10"]))
        for c in claims:
            app.submit(key, decode_steps=MAX_NEW, payload=c)
        ex = LiveExecutor(sched, step_fns={key: make_pff_step_fn()})
        ex.run()
        tok = ByteTokenizer(cfg.vocab_size)
        preds = [stream_verdict(tok, ex.results[r.request_id])
                 for r in app.requests]
        assert len(preds) == len(claims)
        assert all(p in ("SUPPORTED", "REFUTED", "NOT ENOUGH INFO")
                   for p in preds)
        assert 0.0 <= accuracy(preds, claims) <= 1.0
        assert sched.completed_inferences == len(claims) * MAX_NEW
        recs = app.records()
        assert len(recs) == len(claims)
        assert all(not r.exclusive for r in recs)
        assert all(r.ttfs_s >= 0 and r.queue_wait_s >= 0 for r in recs)
        assert sched.admissions > 0, \
            "later claims must be admitted into the live batch"
        # slot budgets from measured memory: the live run must have fed the
        # REAL per-slot cache footprint back into the recipe, displacing
        # the KV_BYTES_PER_PARAM analytic estimate
        assert recipe.measured_slot_bytes > 0
        assert recipe.decode_slot_bytes(1.71e9) == recipe.measured_slot_bytes

    def test_stream_predictions_deterministic(self, setup):
        """Two runs with different worker counts give identical verdicts
        (continuous batching must not change RESULTS, only timing)."""
        cfg, claims, recipe, _ = setup

        def run(workers):
            sched = Scheduler()
            app = Application(sched)
            key = app.register(recipe)
            for _ in range(workers):
                sched.add_worker(Worker(GPU_CATALOG["NVIDIA A10"]))
            for c in claims:
                app.submit(key, decode_steps=MAX_NEW, payload=c)
            ex = LiveExecutor(sched, step_fns={key: make_pff_step_fn()})
            ex.run()
            tok = ByteTokenizer(cfg.vocab_size)
            return [stream_verdict(tok, ex.results[r.request_id])
                    for r in app.requests]

        assert run(1) == run(2)

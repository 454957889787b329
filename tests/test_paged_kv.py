"""Paged KV cache with refcounted shared-prefix reuse: lifecycle + exactness.

The invariants the paged layout must hold under live churn:

* refcounts never go negative; every page is freed exactly once and the
  prefix index is purged with it;
* a shared page is NEVER freed (or recycled) while any tenant still maps
  it — one holder finishing must not disturb the others' tokens;
* a tenant whose ring wraps into a shared page COPIES it first
  (copy-on-write) instead of corrupting the other holders;
* admission maps an indexed prefix by reference: zero prefill tokens and
  zero new pages for the shared span;
* the decode step still compiles ONCE per pool capacity with paging on;
* paged greedy tokens equal the full-forward reference's under
  membership churn, and the engine's contiguous ring's across a ring
  wrap;
* a checkpoint leaves its request decoding, and its snapshot resumes
  token-exactly in another decoder;
* the slot pool refuses every cache the pages cannot hold.

Plus the three bugfix regressions riding along: over-length prompts
raise or set the ``truncated`` flag (never a silent clip), cache growth
carries UNKNOWN cache keys (a layout the grower doesn't know about must
survive ``_grow``), and ``make_pff_step_fn`` frees decoder state for
requests the scheduler pulled out of the batch mid-flight.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax

from repro.configs import get_smoke_config
from repro.inference.streaming import (PagePool, PrefixIndex,
                                       StreamingDecoder, make_pff_step_fn,
                                       snapshot_kv_bytes)
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm2-1.7b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(cfg, params, **kw):
    kw.setdefault("max_len", 48)
    kw.setdefault("page_size", 8)
    return StreamingDecoder(cfg, params, None, None, prompt_len=48, **kw)


def _prompts(rng, cfg, n, shared_len=24):
    """n prompts, even rids share a ``shared_len`` prefix, odd are
    private; tails/lengths all distinct."""
    shared = list(rng.integers(4, cfg.vocab_size, shared_len))
    out = {}
    for rid in range(n):
        tail = list(rng.integers(4, cfg.vocab_size, 3 + rid))
        out[rid] = shared + tail if rid % 2 == 0 else \
            list(rng.integers(4, cfg.vocab_size, 10 + rid))
    return out


def _run(dec, prompts, script):
    """Drive ``dec`` through (rids, finish_after) steps; returns tokens."""
    out = {}
    for rids, fins in script:
        for r in rids:
            if r not in dec._tokens:
                dec.ensure_tokens(r, prompts[r])
        for r, t in dec.step(rids).items():
            out.setdefault(r, []).append(t)
        for r in fins:
            dec.finish(r)
    return out


def _ring_greedy(cfg, params, prompt, n, max_len):
    """``n`` greedy tokens of one prompt through the engine's contiguous
    ring (``M.prefill``, then ``M.decode_step``): a reference that shares
    no code with the page pool."""
    step = jax.jit(functools.partial(M.decode_step, cfg))
    logits, cache = M.prefill(
        cfg, params, {"tokens": np.asarray([prompt], np.int32)}, max_len)
    out = []
    for _ in range(n):
        out.append(int(np.argmax(np.asarray(logits)[0, -1])))
        logits, cache = step(params, cache, np.asarray([[out[-1]]], np.int32))
    return out


CHURN = [
    ([0, 1], []), ([0, 1, 2], []), ([0, 1, 2, 4], [1]),
    ([0, 2, 4], [0]), ([2, 4, 6], []), ([2, 4, 6, 3], [2]),
    ([4, 6, 3, 5], [4]), ([6, 3, 5], [6, 3]), ([5, 7], []),
    ([5, 7], [5, 7]),
]


class TestPagePool:
    def test_refcount_lifecycle(self):
        pool = PagePool(4)
        assert pool.free == 3                      # page 0 is trash
        a = pool.alloc()
        assert a != PagePool.TRASH and pool.refcount(a) == 1
        pool.incref(a)
        assert pool.refcount(a) == 2
        assert pool.decref(a) is False             # still held
        assert pool.refcount(a) == 1
        assert pool.decref(a) is True              # freed now
        assert pool.refcount(a) == 0 and pool.free == 3

    def test_refcounts_never_negative(self):
        pool = PagePool(3)
        a = pool.alloc()
        pool.decref(a)
        with pytest.raises(AssertionError):
            pool.decref(a)                         # double free asserts

    def test_trash_page_never_allocated(self):
        pool = PagePool(3)
        got = {pool.alloc(), pool.alloc()}
        assert PagePool.TRASH not in got
        with pytest.raises(IndexError):
            pool.alloc()                           # exhausted, trash stays

    def test_grow_adds_free_pages(self):
        pool = PagePool(2)
        pool.alloc()
        pool.grow(5)
        assert pool.free == 3 and pool.n_pages == 5


class TestPrefixIndex:
    def test_longest_whole_page_match(self):
        idx = PrefixIndex()
        toks = list(range(20))
        idx.insert(toks, 8, [5, 6])                # two full pages of 8
        assert idx.lookup(toks, 8, 2) == [5, 6]
        assert idx.lookup(toks, 8, 1) == [5]       # caller's tail cap
        assert idx.lookup(toks[:12], 8, 1) == [5]  # shorter prompt, 1 page
        assert idx.lookup(list(range(1, 21)), 8, 2) == []

    def test_forget_page_purges_chains(self):
        idx = PrefixIndex()
        toks = list(range(24))
        idx.insert(toks, 8, [5, 6, 7])
        idx.forget_page(6)                         # middle page dies
        assert idx.lookup(toks, 8, 3) == [5]       # 1-page chain survives
        idx.forget_page(5)
        assert idx.lookup(toks, 8, 3) == []
        assert len(idx) == 0

    def test_first_insert_wins(self):
        idx = PrefixIndex()
        toks = list(range(8))
        idx.insert(toks, 8, [3])
        idx.insert(toks, 8, [9])                   # duplicate content
        assert idx.lookup(toks, 8, 1) == [3]


class TestPagedDecoder:
    def test_churn_token_exact_vs_slot_and_full(self, setup):
        cfg, params = setup
        rng = np.random.default_rng(3)
        prompts = _prompts(rng, cfg, 8)
        paged = _run(_mk(cfg, params), prompts, CHURN)
        full = _run(_mk(cfg, params, slot_cached=False), prompts, CHURN)
        assert paged == full

    def test_all_pages_freed_and_index_purged_after_churn(self, setup):
        cfg, params = setup
        dec = _mk(cfg, params)
        _run(dec, _prompts(np.random.default_rng(4), cfg, 8), CHURN)
        assert dec.pages.in_use == 0
        assert dec.pages.free == dec.pages.n_pages - 1
        assert len(dec.prefix) == 0
        assert len(dec.pool) == 0

    def test_admission_maps_shared_prefix_by_reference(self, setup):
        """Second tenant of a 24-token (3 full pages of 8) prefix pays
        only its tail: no prefix prefill tokens, no new prefix pages."""
        cfg, params = setup
        rng = np.random.default_rng(5)
        prompts = _prompts(rng, cfg, 4)
        dec = _mk(cfg, params)
        dec.ensure_tokens(0, prompts[0])
        dec.step([0])
        t0, p0 = dec.prefill_tokens_total, dec.pages.in_use
        dec.ensure_tokens(2, prompts[2])
        dec.step([0, 2])
        tail = len(prompts[2]) - 24
        assert dec.shared_tokens_total == 24
        assert dec.prefill_tokens_total - t0 <= tail + 7   # bucket pad only
        assert dec.pages.in_use - p0 == -(-tail // 8)      # tail pages only
        shared = [p for p in range(1, dec.pages.n_pages)
                  if dec.pages.refcount(p) > 1]
        assert len(shared) == 3

    def test_shared_page_survives_one_holders_finish(self, setup):
        """Producer finishes; the consumer still maps the prefix pages —
        they must stay allocated and its tokens must stay exact."""
        cfg, params = setup
        rng = np.random.default_rng(6)
        prompts = _prompts(rng, cfg, 4)
        dec = _mk(cfg, params)
        ref = _mk(cfg, params, slot_cached=False)
        script = [([0], []), ([0, 2], []), ([0, 2], [0]),
                  ([2], []), ([2], []), ([2], [2])]
        got = _run(dec, prompts, script)
        # after rid 0 finished, rid 2 still held the 3 prefix pages alone
        assert got == _run(ref, prompts, script)
        assert dec.pages.in_use == 0               # and all freed at the end

    def test_copy_on_write_on_ring_wrap(self, setup):
        """Two tenants share a prefix; both generate past the ring length
        so their writes WRAP into the shared pages.  Each must copy first
        — tokens stay equal to each tenant's own run through the engine's
        contiguous ring of the same T, and while both are live the shared
        pages get un-shared."""
        cfg, params = setup
        rng = np.random.default_rng(7)
        shared = list(rng.integers(4, cfg.vocab_size, 16))
        prompts = {0: shared + list(rng.integers(4, cfg.vocab_size, 5)),
                   1: shared + list(rng.integers(4, cfg.vocab_size, 3))}
        # T = 24 for the pages and the ring: wrap after ~8 generated tokens
        paged = _mk(cfg, params, max_len=24)
        script = [([0], []), ([0, 1], [])] + [([0, 1], [])] * 12
        got = _run(paged, prompts, script)
        n_shared_mid = len([p for p in range(1, paged.pages.n_pages)
                            if paged.pages.refcount(p) > 1])
        assert n_shared_mid == 0, "wrap must have COW'd the shared pages"
        assert [len(got[r]) for r in (0, 1)] == [14, 13]
        assert got == {r: _ring_greedy(cfg, params, prompts[r], len(got[r]),
                                       max_len=24) for r in (0, 1)}
        for r in (0, 1):
            paged.finish(r)
        assert paged.pages.in_use == 0

    def test_decode_compiles_once_per_capacity(self, setup):
        """Recompile audit with paging ON: whatever the admissions, COWs
        and table rewrites, decode has ONE compiled shape per capacity."""
        cfg, params = setup
        dec = _mk(cfg, params, b_max=4)
        _run(dec, _prompts(np.random.default_rng(8), cfg, 8), CHURN)
        decode_shapes = [s for s in dec._shapes if s[0] == "decode"]
        assert decode_shapes == [("decode", 4)]

    # each admission: (shared, private) token counts per request, all in
    # one step; the prefill grid (rows, pool capacity) it must pad to
    CHUNKED = {
        # 4 + 1 + 2 chunk rows, 4 slots: padded to 8; the sharers map the
        # writer's 3 pages in the launch that writes them
        "writer_and_two_sharers": ([(24, 5), (24, 3), (24, 10)], (8, 4)),
        # the writer's one whole page shared, then a 20-token tail over
        # 3 pages: 2 + 3 rows, 2 slots, padded to 6
        "tail_spans_3_pages": ([(8, 2), (8, 20)], (6, 2)),
        # 3 single-page prompts, 4 slots: padded to the power of two 4
        "pads_rows": ([(0, 5), (0, 6), (0, 7)], (4, 4)),
    }

    @pytest.mark.parametrize("arch", ["smollm2-1.7b", "granite-3-8b"])
    @pytest.mark.parametrize("case", sorted(CHUNKED))
    def test_chunked_admission_token_exact(self, arch, case):
        """An admission laid out one page per row is token-exact against
        the full-forward reference, at admission and through 8 decode
        steps, for MHA (smollm2) and GQA with its multipliers (Granite);
        every slot's position is its prompt length, however many rows
        repeated the slot."""
        cfg = get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(1))
        rng = np.random.default_rng(11)
        spec, (rows, cap) = self.CHUNKED[case]
        shared = list(rng.integers(4, cfg.vocab_size, 24))
        prompts = {r: shared[:n] + list(rng.integers(4, cfg.vocab_size, m))
                   for r, (n, m) in enumerate(spec)}
        rids = list(prompts)
        script = [(rids, [])] * 9
        dec = _mk(cfg, params)
        got = _run(dec, prompts, script[:1])
        assert ("prefill", rows, 8, cap) in dec._shapes
        assert dec.pool.capacity == cap
        pos = np.asarray(dec._cache["pos"])
        assert [int(pos[dec.pool.slot_of[r]]) for r in rids] == \
            [len(prompts[r]) for r in rids]
        assert dec.shared_tokens_total == sum(
            n // 8 * 8 for n, _m in spec[1:])
        for r, toks in _run(dec, prompts, script[1:]).items():
            got[r] += toks
        ref = _run(_mk(cfg, params, slot_cached=False), prompts, script)
        assert got == ref

    def test_measured_slot_bytes_is_page_budget(self, setup):
        """The per-slot bytes follow from the pool's shapes: 0 before the
        cache exists, then the worst case ``max_pages * page_bytes``; the
        retention budget is set in pages when the pool is made."""
        cfg, params = setup
        dec = _mk(cfg, params)
        assert dec.measured_slot_bytes == 0 and dec.kv_bytes_in_use == 0
        dec.ensure_tokens(0, list(range(4, 24)))
        dec.step([0])
        assert dec.page_bytes > 0
        assert dec.measured_slot_bytes == dec.max_pages * dec.page_bytes
        assert dec.kv_bytes_in_use == dec.pages.in_use * dec.page_bytes
        assert dec.pages.retained_cap == 0
        kept = _mk(cfg, params, retain_bytes=3 * dec.page_bytes + 1)
        kept.ensure_tokens(0, list(range(4, 24)))
        kept.step([0])
        assert kept.pages.retained_cap == 3
        assert kept.measured_slot_bytes == dec.measured_slot_bytes

    def test_checkpoint_keeps_decoding_and_resumes_elsewhere(self, setup):
        """``checkpoint`` copies a row out without touching it: the source
        decodes on with its page refcounts unchanged, and the snapshot,
        adopted and resumed by a second decoder, continues with the
        uninterrupted run's tokens.  Rid 2 shares rid 0's prefix pages,
        so the snapshot holds shared pages too."""
        cfg, params = setup
        prompts = _prompts(np.random.default_rng(12), cfg, 4)
        steps, at = 10, 4
        ref = _mk(cfg, params)
        want = _run(ref, prompts, [([0, 2], [])] * steps)[2]
        src = _mk(cfg, params)
        got = _run(src, prompts, [([0, 2], [])] * at)[2]
        refs = {p: src.pages.refcount(p) for p in range(1, src.pages.n_pages)}
        snap = src.checkpoint(2)
        assert src.checkpoint(9) is None           # no bound row
        assert src.pool.slot_of.get(2) is not None
        assert refs == {p: src.pages.refcount(p)
                        for p in range(1, src.pages.n_pages)}
        assert snap["pos"] == len(prompts[2]) + at - 1
        assert src.kv_ckpt_bytes_total == snapshot_kv_bytes(snap) > 0
        rest = _run(src, prompts, [([0, 2], [])] * (steps - at))[2]
        assert got + rest == want                  # the source decodes on
        dst = _mk(cfg, params)
        assert dst.adopt(2, snap) == snapshot_kv_bytes(snap)
        assert dst.resume(2) == snapshot_kv_bytes(snap)
        assert dst.kv_adopt_bytes_total == snapshot_kv_bytes(snap)
        assert dst.kv_resume_bytes_total == 0
        resumed = [dst.step([2])[2] for _ in range(steps - at)]
        assert got + resumed == want
        assert dst.finish(2) == want
        assert dst.pages.in_use == 0

    @pytest.mark.parametrize("arch, kv_cache_dtype, paged", [
        ("llava-next-34b", "", None), ("hymba-1.5b", "", None),
        ("xlstm-350m", "", None), ("whisper-small", "", None),
        ("deepseek-v3-671b", "", None), ("smollm2-1.7b", "int8", None),
        ("smollm2-1.7b", "", False)],
        ids=["vlm", "hybrid", "ssm", "audio", "mla", "int8", "paged_false"])
    def test_refuses_caches_the_pages_cannot_hold(self, arch,
                                                   kv_cache_dtype, paged):
        """The slot pool refuses, by name, every configuration whose cache
        the pages cannot hold, and ``paged=False``; the full-forward
        reference still takes every family."""
        cfg = dataclasses.replace(get_smoke_config(arch),
                                  kv_cache_dtype=kv_cache_dtype)
        with pytest.raises(ValueError) as err:
            StreamingDecoder(cfg, None, None, None, paged=paged)
        if paged is None:
            assert not M.supports_paging(cfg)
            assert cfg.arch_id in str(err.value)
            assert "2.8" in str(err.value)
            StreamingDecoder(cfg, None, None, None, slot_cached=False)
        else:
            assert "paged=False" in str(err.value)


class TestBugfixRegressions:
    def test_overlong_prompt_strict_raises(self, setup):
        cfg, params = setup
        dec = _mk(cfg, params, strict_prompts=True)
        with pytest.raises(ValueError, match="caps prompts"):
            dec.ensure_tokens(0, list(range(4, 4 + 80)))
        assert 0 not in dec._tokens                # nothing half-admitted

    def test_overlong_prompt_sets_truncated_flag(self, setup):
        cfg, params = setup
        dec = _mk(cfg, params)
        dec.ensure_tokens(0, list(range(4, 4 + 80)))
        dec.ensure_tokens(1, list(range(4, 24)))
        assert dec.truncated[0] is True
        assert dec.truncated[1] is False
        assert len(dec._tokens[0]) == dec.max_len  # clipped, not dropped
        dec.step([0, 1])
        dec.finish(0)
        assert 0 not in dec.truncated              # state fully released

    def test_grow_preserves_unknown_cache_keys(self, setup):
        """_grow must rebuild the cache GENERICALLY: keys the initialiser
        does not produce (here a fake sampling-state leaf) ride across
        growth with their prefix contents intact — and live requests
        keep decoding exactly."""
        cfg, params = setup
        rng = np.random.default_rng(9)
        prompts = _prompts(rng, cfg, 6)
        dec = _mk(cfg, params, b_max=2)
        ref = _mk(cfg, params, slot_cached=False)
        script = [([0, 1], [])] * 2
        got = _run(dec, prompts, script)
        marker = jax.numpy.arange(7, dtype=jax.numpy.float32)
        dec._cache["rng_state"] = marker           # a key _grow doesn't know
        script2 = [([0, 1, 2, 3], [])] * 2 + [([0, 1, 2, 3], [0, 1, 2, 3])]
        got2 = _run(dec, prompts, script2)
        assert dec.pool.capacity == 4              # growth happened
        assert "rng_state" in dec._cache
        np.testing.assert_array_equal(np.asarray(dec._cache["rng_state"]),
                                      np.asarray(marker))
        full = _run(ref, prompts, script + script2)
        merged = {r: got.get(r, []) + got2.get(r, []) for r in full}
        assert merged == full

    def test_step_fn_frees_state_for_requeued_members(self, setup):
        """A rid that was stepping here and then VANISHES from members
        (requeued/migrated by the scheduler) must have its slot, pages
        and token buffers freed — not leak until teardown."""
        from repro.cluster.scheduler import Request

        cfg, params = setup

        class _Tok:                                # identity tokenizer
            def encode(self, text):
                return list(text)

        class _Tpl:
            def render(self, claim):
                return claim

        class _Eng:
            def __init__(self):
                self.cfg, self.params = cfg, params

        payloads = {"xla_executable": _Eng(),
                    "context_inputs": {"tokenizer": _Tok(),
                                       "template": _Tpl()}}
        step_fn = make_pff_step_fn(prompt_len=16, max_len=32)
        reqs = {i: Request(recipe_key="k", decode_steps=8,
                           payload=[4 + i] * (10 + i)) for i in range(3)}
        def run(members):                          # the executor's loop
            step_fn(payloads, members)
            for r in members:
                r.steps_done += 1

        members = [reqs[0], reqs[1]]
        run(members)
        dec = payloads["_stream_decoder"]
        assert set(dec.active_rids()) == {r.request_id for r in members}
        # rid 0 requeued away; rid 2 joins
        run([reqs[1], reqs[2]])
        live = {reqs[1].request_id, reqs[2].request_id}
        assert set(dec.active_rids()) == live
        assert set(dec.pool.slot_of) == live
        held = {int(p) for row in dec._table for p in row if p}
        assert dec.pages.in_use == len(held)      # rid 0's pages came back
        # drain everyone: step_fn's own finish path frees the rest
        for _ in range(8):
            run([reqs[1], reqs[2]])
        assert dec.active_rids() == []
        assert dec.pages.in_use == 0

    def test_truncated_flag_reaches_request(self, setup):
        """make_pff_step_fn surfaces the decoder's clip onto the Request,
        which the scheduler copies into its RequestRecord."""
        from repro.cluster.scheduler import Request

        cfg, params = setup

        class _Tok:
            def encode(self, text):
                return list(text)

        class _Tpl:
            def render(self, claim):
                return claim

        class _Eng:
            def __init__(self):
                self.cfg, self.params = cfg, params

        payloads = {"xla_executable": _Eng(),
                    "context_inputs": {"tokenizer": _Tok(),
                                       "template": _Tpl()}}
        step_fn = make_pff_step_fn(prompt_len=8, max_len=32)
        long_req = Request(recipe_key="k", decode_steps=4,
                           payload=[4] * 50)       # 50 > prompt_len=8
        short_req = Request(recipe_key="k", decode_steps=4,
                            payload=[4] * 6)
        step_fn(payloads, [long_req, short_req])
        assert long_req.truncated is True
        assert short_req.truncated is False

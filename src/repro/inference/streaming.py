"""Live continuous-batching decoder: a persistent slot pool over KV pages.

The LIVE leg of the request-stream redesign.  A library's dynamic batch
changes membership between decode steps, so the device batch cannot be a
fixed (B, S) array compiled once per task.  :class:`StreamingDecoder`
keeps the decode state RESIDENT on the device instead: a
:class:`SlotPool` of ``capacity`` rows that requests bind to on
admission and free on completion, over one shared pool of KV pages.

* **admit** — a new request's unshared prompt tail runs through
  ``M.prefill_into_pages``, which writes its K/V into the row's pages
  and sets its position without touching live rows;
* **step** — ONE cached ``M.decode_step`` over all slots advances every
  active row by one token at O(1) FLOPs/token (each row embeds/RoPEs at
  its own position, writes at its own ring slot, masks at its own
  length via the paged decode-attention kernel);
* **finish** — the slot returns to the free list and its pages are
  unmapped from the page table, so reuse never leaks context across
  requests.

Paged KV layout
---------------
Every layer's K/V lives in PHYSICAL PAGE POOLS of shape
(L, n_pages, page_size, K, hd) shared by every row, addressed through a
per-row PAGE TABLE:

* ``cache["table"]`` is (B, max_pages) int32.  Row ``b``'s logical ring
  slot ``s`` (s = pos % T, T = max_pages * page_size) lives at physical
  coordinates ``(table[b, s // page_size], s % page_size)``.  Entry 0 is
  the UNMAPPED sentinel: physical page 0 is reserved as the trash page
  — never allocated, never attended (it always sits past ``n_valid``),
  and the landing zone for masked lock-step writes.
* :class:`PagePool` owns the physical pages host-side with REFCOUNTS.
  ``alloc`` → refcount 1; admission of a request whose prompt prefix is
  already resident increfs the shared pages instead of recomputing
  them; ``finish`` decrefs every mapped page and frees at zero.
* :class:`PrefixIndex` maps EXACT token tuples (no hashing collisions:
  the key is the tuple itself) of whole-page prompt prefixes to the
  page chain holding them.  On admission the longest indexed prefix —
  capped at ``(prompt_len - 1) // page_size`` pages so the tail is
  never empty and the first-token logits still come from this
  request's own prefill — is mapped by reference (refcount++, ZERO
  prefill FLOPs, ZERO new KV bytes) and only the unshared tail runs
  through ``M.prefill_into_pages``.  Index entries are purged when
  their page is freed or overwritten in place (ring wrap), so a hit
  can never alias stale bytes.
* Copy-on-write: decode writes land in the page holding slot
  ``pos % T``.  Before each step ``_ensure_writable`` allocates a fresh
  page when that entry is unmapped, and COPIES the page (then decrefs
  the original) when its refcount is > 1 — a tenant wrapping its ring
  into a shared prefix page never corrupts the other holders.

The pages hold GQA K/V only (``M.supports_paging``): the slot pool
refuses recurrent, MLA, sliding-window, int8, vision and
cross-attention caches rather than serve them in another layout.

Compiled-shape accounting: the decode step compiles once per pool
capacity (capacities grow by doubling) — the page table is a cache
VALUE, not a shape — and prefill once per count of page-wide chunk rows
(each unshared tail is admitted one page per row, so the row width is
always the page size).  The per-slot cache bytes
(``measured_slot_bytes``) are the worst case ``max_pages * page_bytes``
a row can pin, fed back into ``ContextRecipe.decode_slot_bytes`` by the
live executor when sizing slot budgets.

Over-length prompts are never silently truncated any more: with
``strict_prompts=True`` admission raises; otherwise the prompt is
clipped and the request's ``truncated`` flag (surfaced through
``RequestRecord``) records it.

The pre-slot full-forward path (prompt + generated prefix re-run
through ``M.forward`` every step; right-padding inert under causal
attention) survives as ``slot_cached=False``, for any family — the
token-exactness reference the slot pool is asserted against in
tests/test_streaming_live.py and tests/test_paged_kv.py.
"""
from __future__ import annotations

import functools
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from ..data.prompts import parse_verdict
from ..data.tokenizer import PAD
from ..models import model as M
from ..tracing import span
from .pff import PROMPT_LEN


def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _named(fn, *args, **kwargs):
    """``fn`` with ``args`` bound, keeping ``fn``'s name, so its jitted
    program reads ``jit_<name>`` in a profile (a bare partial reads
    ``jit__unknown``)."""
    bound = functools.partial(fn, *args, **kwargs)
    bound.__name__ = fn.__name__
    return bound


def copy_page(stages, dst, src):
    """Every layer's KV page ``src`` copied onto page ``dst``."""
    return jax.tree_util.tree_map(lambda x: x.at[:, dst].set(x[:, src]),
                                  stages)


def _nbytes(tree) -> int:
    return int(sum(x.nbytes for x in jax.tree_util.tree_leaves(tree)))


def snapshot_kv_bytes(snap: dict) -> int:
    """Bytes of a host snapshot's K/V (see ``StreamingDecoder.suspend``)."""
    return _nbytes(snap["kv"])


class SlotPool:
    """Fixed-capacity allocator binding request ids to cache rows."""

    def __init__(self, capacity: int = 0):
        self.capacity = capacity
        self.slot_of: Dict[int, int] = {}
        self._free: List[int] = list(range(capacity - 1, -1, -1))

    def bind(self, rid: int) -> int:
        slot = self._free.pop()
        self.slot_of[rid] = slot
        return slot

    def release(self, rid: int) -> Optional[int]:
        slot = self.slot_of.pop(rid, None)
        if slot is not None:
            self._free.append(slot)
        return slot

    def grow(self, capacity: int) -> None:
        assert capacity >= self.capacity
        self._free[:0] = range(capacity - 1, self.capacity - 1, -1)
        self.capacity = capacity

    @property
    def free(self) -> int:
        return len(self._free)

    def __len__(self) -> int:
        return len(self.slot_of)


class PagePool:
    """Refcounted allocator over the physical KV pages.

    Page 0 is the reserved TRASH page: it is never handed out, doubles
    as the unmapped page-table sentinel, and absorbs masked lock-step
    writes.  Refcounts are host-side only — the device sees pages purely
    through the table.

    PREFIX RETENTION (``retained_cap`` > 0): a page whose refcount hits
    zero is PARKED in an LRU of at most ``retained_cap`` pages instead
    of freed — its bytes stay valid device-side and its prefix-index
    entries survive, so shared-prefix reuse works across GAPS in time,
    not just overlap.  ``incref`` revives a parked page (an index hit on
    a retained prefix); parked pages are reclaimed only under pressure:
    LRU-first when ``alloc`` finds the free list empty, or when the park
    itself overflows the cap.  Reclaiming fires ``on_evict_retained``
    (the decoder wires it to ``PrefixIndex.forget_page``) — index
    entries purge on ACTUAL free, never on park.  ``retained_cap=0``
    (default) frees at zero exactly as before."""

    TRASH = 0

    def __init__(self, n_pages: int, retained_cap: int = 0):
        assert n_pages >= 1
        self.n_pages = n_pages
        self.retained_cap = retained_cap
        self.on_evict_retained = None     # callback(page) on actual free
        self._ref: Dict[int, int] = {}
        self._retained: "OrderedDict[int, None]" = OrderedDict()
        self._free: List[int] = list(range(n_pages - 1, 0, -1))

    def _reclaim_lru(self) -> int:
        """Actually free the least-recently-parked page."""
        page, _ = self._retained.popitem(last=False)
        if self.on_evict_retained is not None:
            self.on_evict_retained(page)
        return page

    def alloc(self) -> int:
        if not self._free and self._retained:
            page = self._reclaim_lru()    # allocation pressure: evict LRU
        else:
            page = self._free.pop()
        self._ref[page] = 1
        return page

    def incref(self, page: int) -> None:
        assert page != self.TRASH
        if page in self._retained:        # prefix hit on a parked page
            del self._retained[page]
            self._ref[page] = 1
            return
        assert page in self._ref
        self._ref[page] += 1

    def decref(self, page: int) -> bool:
        """Drop one reference; returns True when the page was freed
        (a parked page is NOT freed — its bytes remain valid)."""
        assert page != self.TRASH
        assert self._ref.get(page, 0) > 0, \
            f"decref of unreferenced page {page} (double free)"
        self._ref[page] -= 1
        if self._ref[page] == 0:
            del self._ref[page]
            if self.retained_cap > 0:
                self._retained[page] = None
                while len(self._retained) > self.retained_cap:
                    self._free.append(self._reclaim_lru())
                return False
            self._free.append(page)
            return True
        return False

    def refcount(self, page: int) -> int:
        return self._ref.get(page, 0)

    def grow(self, n_pages: int) -> None:
        assert n_pages >= self.n_pages
        self._free[:0] = range(n_pages - 1, self.n_pages - 1, -1)
        self.n_pages = n_pages

    @property
    def free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return len(self._ref)

    @property
    def retained_count(self) -> int:
        return len(self._retained)


class PrefixIndex:
    """Exact-match index from whole-page prompt prefixes to page chains.

    Keys are the literal token TUPLES of the first ``j * page_size``
    prompt tokens (j = 1..n_full_pages) — exact equality, so a hit can
    never be a hash collision.  Values are the physical page chains
    holding those tokens.  ``forget_page`` removes every entry whose
    chain references a page (called when the page is freed or about to
    be overwritten in place), keeping the index free of stale bytes."""

    def __init__(self):
        self._chains: Dict[Tuple[int, ...], Tuple[int, ...]] = {}
        self._keys_of: Dict[int, Set[Tuple[int, ...]]] = {}

    def insert(self, tokens: Sequence[int], page_size: int,
               pages: Sequence[int]) -> None:
        """Register every whole-page prefix of ``tokens`` (first wins)."""
        n_full = min(len(tokens) // page_size, len(pages))
        for j in range(1, n_full + 1):
            key = tuple(tokens[:j * page_size])
            if key in self._chains:
                continue
            chain = tuple(int(p) for p in pages[:j])
            self._chains[key] = chain
            for p in chain:
                self._keys_of.setdefault(p, set()).add(key)

    def lookup(self, tokens: Sequence[int], page_size: int,
               max_pages: int) -> List[int]:
        """Longest indexed whole-page prefix of ``tokens``, at most
        ``max_pages`` pages (callers cap at (len-1)//page_size so the
        unshared tail is never empty)."""
        best: Tuple[int, ...] = ()
        for j in range(1, max_pages + 1):
            chain = self._chains.get(tuple(tokens[:j * page_size]))
            if chain is None:
                break                    # prefixes are registered in chains
            best = chain
        return list(best)

    def forget_page(self, page: int) -> None:
        for key in self._keys_of.pop(page, ()):
            chain = self._chains.pop(key, ())
            for p in chain:
                if p != page and p in self._keys_of:
                    self._keys_of[p].discard(key)

    def __len__(self) -> int:
        return len(self._chains)


class StreamingDecoder:
    """Greedy decoder over a membership-changing request batch.

    ``slot_cached=True`` (default): persistent slot-pool decode over KV
    pages, O(1) per token; it raises ``ValueError`` for a family whose
    cache the pages cannot hold (``M.supports_paging``).
    ``slot_cached=False``: the full-forward reference path, O(S) per
    token, for any family.  Both produce identical greedy tokens while
    sequences stay within ``max_len`` (asserted in tests under
    membership churn).

    ``paged`` accepts only ``None`` and ``True``: the pages are the one
    KV layout.  It is kept for callers that still pass ``paged=True``
    and goes once they stop.

    ``b_max`` pre-sizes the pool (typically the library's slot budget, so
    the decode step compiles exactly once); it is a sizing hint, not a
    hard cap — if the scheduler ever admits beyond it the pool doubles
    rather than dropping in-flight requests.
    """

    def __init__(self, cfg, params, tokenizer, template, *,
                 prompt_len: int = PROMPT_LEN, slot_cached: bool = True,
                 max_len: Optional[int] = None, b_max: Optional[int] = None,
                 paged: Optional[bool] = None, page_size: int = 64,
                 strict_prompts: bool = False, retain_bytes: int = 0):
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.template = template
        self.prompt_len = prompt_len
        self.slot_cached = slot_cached
        self.max_len = max_len or prompt_len + 64
        self.strict_prompts = strict_prompts
        if paged not in (None, True):
            raise ValueError(f"paged={paged!r}: the KV pages are the "
                             "decoder's only layout")
        if slot_cached and not M.supports_paging(cfg):
            raise ValueError(
                f"{cfg.arch_id} ({cfg.family} family) keeps a cache the KV "
                "pages cannot hold (recurrent, MLA, sliding-window, int8, "
                "vision or cross-attention); the slot pool serves it once "
                "the one page manager covers it (ROADMAP.md item 2.8); "
                "slot_cached=False runs the full-forward reference")
        self.page_size = page_size
        self.max_pages = -(-self.max_len // page_size)
        self.pages: Optional[PagePool] = None
        self.page_bytes = 0                       # per page, all layers
        self.prefix = PrefixIndex()
        self._table: Optional[np.ndarray] = None  # host page table mirror
        self._table_dirty = False
        self._tokens: Dict[int, List[int]] = {}   # rid -> prompt+generated
        self._prompt_end: Dict[int, int] = {}
        self.truncated: Dict[int, bool] = {}      # rid -> prompt was clipped

        def forward(p, toks):
            return M.forward(cfg, p, {"tokens": toks})

        self._fwd = jax.jit(forward)
        self._decode = jax.jit(_named(M.decode_step, cfg))
        self._prefill_pages = jax.jit(_named(M.prefill_into_pages, cfg))
        self._copy_page = jax.jit(copy_page)
        self._shapes: set = set()                 # compile-shape audit
        self.pool = SlotPool(b_max or 0)
        self._cache = None                        # device cache pytree
        self.prefill_tokens_total = 0             # admission cost counter
        self.shared_tokens_total = 0              # prefix tokens reused
        # prefix-page retention budget (bytes of refcount-zero pages to
        # park, see PagePool); 0 = free-at-zero, the pre-retention path
        self.retain_bytes = retain_bytes
        # rid -> host-side KV snapshot (preemption suspend/resume)
        self._suspended: Dict[int, dict] = {}
        self.kv_suspend_bytes_total = 0           # spill-path byte meters
        self.kv_resume_bytes_total = 0
        # snapshots received from ANOTHER decoder (KV_SHIP): their restore
        # bytes are a handoff landing, not a preemption resume, and must
        # not pollute the spill/resume parity meters
        self._adopted: set = set()
        self.kv_adopt_bytes_total = 0
        self.kv_ckpt_bytes_total = 0              # non-destructive exports

    # -- membership -----------------------------------------------------
    def ensure(self, rid: int, claim) -> None:
        """Admit ``rid``: tokenize its prompt (idempotent)."""
        if rid in self._tokens:
            return
        ids = list(self.tokenizer.encode(self.template.render(claim)))
        self.ensure_tokens(rid, ids, limit=self.prompt_len)

    def ensure_tokens(self, rid: int, token_ids: List[int], *,
                      limit: Optional[int] = None) -> None:
        """Admit ``rid`` with pre-tokenized prompt ids (idempotent).

        Prompts longer than ``limit`` (default: the ``max_len`` ring)
        RAISE under ``strict_prompts``; otherwise they are clipped and
        the request's ``truncated`` flag records it — never a silent
        drop."""
        if rid in self._tokens:
            return
        cap = min(limit or self.max_len, self.max_len)
        if len(token_ids) > cap:
            if self.strict_prompts:
                raise ValueError(
                    f"prompt for request {rid} is {len(token_ids)} tokens "
                    f"but the decoder caps prompts at {cap} "
                    f"(prompt_len={self.prompt_len}, max_len={self.max_len})")
            self.truncated[rid] = True
        else:
            self.truncated[rid] = False
        self._tokens[rid] = list(token_ids)[:cap]
        self._prompt_end[rid] = len(self._tokens[rid])

    def active_rids(self) -> List[int]:
        """Requests currently holding decoder state."""
        return list(self._tokens.keys())

    def finish(self, rid: int) -> List[int]:
        """Release ``rid``'s state (slot + page references); returns its
        generated token ids.  Every mapped page is decref'd (freed pages
        purge their prefix-index entries) and the table row reset to
        trash."""
        slot = self.pool.release(rid)
        if slot is not None:
            for p in self._table[slot]:
                p = int(p)
                if p != PagePool.TRASH and self.pages.decref(p):
                    self.prefix.forget_page(p)
            self._table[slot] = PagePool.TRASH
            self._table_dirty = True
        toks = self._tokens.pop(rid, [])
        end = self._prompt_end.pop(rid, len(toks))
        self.truncated.pop(rid, None)
        return toks[end:]

    # -- preemption: KV suspend / resume --------------------------------
    def has_suspended(self, rid: int) -> bool:
        return rid in self._suspended

    def _snapshot(self, rid: int) -> Optional[dict]:
        """Host copy of ``rid``'s decode state, or None when it holds no
        bound row: its token buffer (``tokens``, ``prompt_end``,
        ``truncated``), its row position (``pos``), the logical index of
        each mapped page (``page_idx``) and those pages' K/V (``kv``,
        leaves (L, len(page_idx), page_size, K, hd))."""
        slot = self.pool.slot_of.get(rid)
        if slot is None or rid not in self._tokens or self._cache is None:
            return None
        mapped = [(pi, int(p)) for pi, p in enumerate(self._table[slot])
                  if int(p) != PagePool.TRASH]
        idx = np.asarray([p for _pi, p in mapped], np.int32)
        return {
            "tokens": list(self._tokens[rid]),
            "prompt_end": self._prompt_end[rid],
            "truncated": self.truncated.get(rid, False),
            "pos": int(np.asarray(self._cache["pos"])[slot]),
            "page_idx": [pi for pi, _p in mapped],
            "kv": jax.tree_util.tree_map(lambda x: np.asarray(x[:, idx]),
                                         self._cache["stages"]),
        }

    def suspend(self, rid: int) -> int:
        """Spill ``rid``'s decode state HOST-side and release its device
        footprint (slot + pages), so an interactive request can take the
        slot.  The snapshot (:meth:`_snapshot`) lives in ``_suspended``
        until :meth:`resume` restores it bit-exactly, WITHOUT re-prefill.
        Returns the snapshot's KV byte size (0 if ``rid`` holds no
        slot)."""
        snap = self._snapshot(rid)
        if snap is None:
            return 0
        self.finish(rid)
        self._suspended[rid] = snap
        nbytes = snapshot_kv_bytes(snap)
        self.kv_suspend_bytes_total += nbytes
        return nbytes

    def resume(self, rid: int) -> int:
        """Re-admit a suspended ``rid`` from its host snapshot: bind a
        slot, scatter the saved K/V back onto freshly allocated pages,
        restore the row position — NO prefill runs.  Greedy decode then
        continues bit-exactly where it stopped.  Returns the restored KV
        byte size."""
        snap = self._suspended.pop(rid)
        if self.pool.free == 0:
            self._grow(len(self.pool.slot_of) + 1)
        elif self._cache is None:
            self._cache = self._fresh_cache(self.pool.capacity)
        slot = self.pool.bind(rid)
        self._tokens[rid] = snap["tokens"]
        self._prompt_end[rid] = snap["prompt_end"]
        self.truncated[rid] = snap["truncated"]
        pages = [self.pages.alloc() for _ in snap["page_idx"]]
        self._table[slot] = PagePool.TRASH
        self._table[slot, snap["page_idx"]] = pages
        self._table_dirty = True
        idx = np.asarray(pages, np.int32)
        self._cache["stages"] = jax.tree_util.tree_map(
            lambda big, small: big.at[:, idx].set(small),
            self._cache["stages"], snap["kv"])
        self._sync_table()
        self._cache["pos"] = self._cache["pos"].at[slot].set(snap["pos"])
        nbytes = snapshot_kv_bytes(snap)
        if rid in self._adopted:
            self._adopted.discard(rid)
            self.kv_adopt_bytes_total += nbytes
        else:
            self.kv_resume_bytes_total += nbytes
        return nbytes

    # -- disaggregation: KV_SHIP export / adopt -------------------------
    def export_suspended(self, rid: int) -> Optional[dict]:
        """Hand ``rid``'s host-side snapshot to the caller (the KV_SHIP
        path): ownership leaves this decoder entirely — the destination
        decoder takes it via :meth:`adopt`.  Returns None when ``rid``
        holds no suspended state here (e.g. the library was spilled and
        the snapshot died with it)."""
        self._adopted.discard(rid)
        return self._suspended.pop(rid, None)

    def adopt(self, rid: int, snap: dict) -> int:
        """Receive a snapshot shipped from another decoder's
        :meth:`export_suspended`.  It parks in ``_suspended`` exactly
        like a local suspend, so the next step's ``has_suspended`` path
        restores it WITHOUT re-prefill — decode continues bit-exactly
        from the prefill worker's state.  Restore bytes are accounted to
        ``kv_adopt_bytes_total`` (a handoff, not a preemption resume).
        Both decoders must use the same page size and ``max_len`` (same
        recipe).  Returns the snapshot's KV byte size."""
        self._suspended[rid] = snap
        self._adopted.add(rid)
        return snapshot_kv_bytes(snap)

    # -- crash safety: non-destructive KV checkpoint export -------------
    def checkpoint(self, rid: int) -> Optional[dict]:
        """Export a COPY of ``rid``'s current decode state (the KV_CKPT
        path): the same host-side snapshot :meth:`suspend` builds, but
        the request keeps decoding here — its slot, page mappings and
        refcounts are untouched.  A checkpoint host parks the copy via
        :meth:`adopt`; if this worker later dies, decode resumes
        token-exactly from the snapshot there, losing only the steps
        generated since the export.  Returns None when ``rid`` holds no
        bound slot (nothing to snapshot)."""
        snap = self._snapshot(rid)
        if snap is not None:
            self.kv_ckpt_bytes_total += snapshot_kv_bytes(snap)
        return snap

    # -- the step -------------------------------------------------------
    def step(self, rids: Sequence[int]) -> Dict[int, int]:
        """One greedy decode step for the CURRENT membership.

        Slot mode: one cached ``decode_step`` over the pool advances the
        rows already bound; newly seen rids are admitted via prefill
        (their first token comes from the prefill logits).  Full mode:
        re-form the padded (B, S) batch and run the full forward.
        Returns {rid: new_token}."""
        rids = list(rids)
        if not rids:
            return {}
        if not self.slot_cached:
            return self._step_full(rids)
        active = [r for r in rids if r in self.pool.slot_of]
        fresh = [r for r in rids if r not in self.pool.slot_of]
        out: Dict[int, int] = {}
        if len(fresh) > self.pool.free or (fresh and self._cache is None):
            with span("repro.decoder.grow"):
                if len(fresh) > self.pool.free:
                    self._grow(len(self.pool.slot_of) + len(fresh))
                else:                             # b_max pre-sized the pool
                    self._cache = self._fresh_cache(self.pool.capacity)
        if active:
            out.update(self._decode_active(active))
        if fresh:
            out.update(self._admit(fresh))
        return out

    def _fresh_cache(self, cap: int):
        """Device cache for ``cap`` rows and its host page table; the
        first call also makes the page pool, its retention budget in
        pages from ``retain_bytes``."""
        n_pages = 1 + cap * self.max_pages        # +1: the trash page
        cache = M.paged_cache_init(self.cfg, cap, n_pages, self.page_size,
                                   self.max_pages)
        if self.pages is None:
            self.page_bytes = _nbytes(cache["stages"]) // n_pages
            retained = max(1, self.retain_bytes // self.page_bytes) \
                if self.retain_bytes else 0
            self.pages = PagePool(n_pages, retained_cap=retained)
            # retained pages purge their index entries on ACTUAL free
            self.pages.on_evict_retained = self.prefix.forget_page
        self._table = np.zeros((cap, self.max_pages), np.int32)
        self._table_dirty = False                 # fresh device table is 0 too
        return cache

    def _sync_table(self) -> None:
        if self._table_dirty:
            with span("repro.decoder.table_sync"):
                self._cache["table"] = jax.numpy.asarray(self._table)
            self._table_dirty = False

    @property
    def measured_slot_bytes(self) -> int:
        """Per-slot cache footprint: the ``max_pages * page_bytes`` a row
        can pin at worst (0 until the cache exists)."""
        return self.max_pages * self.page_bytes

    @property
    def kv_bytes_in_use(self) -> int:
        """Bytes actually pinned by live requests (mapped pages count
        ONCE however many rows share them)."""
        return self.pages.in_use * self.page_bytes if self.pages else 0

    # -- paged page lifecycle -------------------------------------------
    def _bind_pages(self, rid: int) -> int:
        """Map ``rid``'s prompt onto pages: the longest indexed prefix by
        reference (refcount++), fresh pages for the rest.  Registers the
        prompt's own whole pages in the index (they are filled by this
        very admission's prefill call) and returns the shared base —
        the number of prompt tokens that will NOT be prefilled."""
        toks = self._tokens[rid]
        P = self.page_size
        n_needed = max(1, -(-len(toks) // P))
        shared = self.prefix.lookup(toks, P, (len(toks) - 1) // P)
        for p in shared:
            self.pages.incref(p)
        pages = list(shared)
        while len(pages) < n_needed:
            pages.append(self.pages.alloc())
        slot = self.pool.slot_of[rid]
        self._table[slot, :len(pages)] = pages
        self._table[slot, len(pages):] = PagePool.TRASH
        self._table_dirty = True
        self.prefix.insert(toks, P, pages)        # whole pages only
        self.shared_tokens_total += len(shared) * P
        return len(shared) * P

    def _ensure_writable(self, rid: int) -> int:
        """Guarantee the page receiving this step's decode write is
        exclusively owned.  Unmapped (ring entered a new page) → alloc;
        shared (ring WRAPPED into a refcounted prefix page) → copy-on-
        write; exclusively owned but indexed → purge the index entry
        (the in-place write is about to change the page's bytes).
        Returns the pages copied: 1 for a copy-on-write, else 0."""
        T = self.max_pages * self.page_size
        pos = len(self._tokens[rid]) - 1          # slot this token writes
        pi = (pos % T) // self.page_size
        slot = self.pool.slot_of[rid]
        page = int(self._table[slot, pi])
        if page == PagePool.TRASH:
            self._table[slot, pi] = self.pages.alloc()
            self._table_dirty = True
            return 0
        if self.pages.refcount(page) > 1:
            fresh = self.pages.alloc()
            self._cache["stages"] = self._copy_page(
                self._cache["stages"], np.int32(fresh), np.int32(page))
            if self.pages.decref(page):
                self.prefix.forget_page(page)
            self._table[slot, pi] = fresh
            self._table_dirty = True
            return 1
        self.prefix.forget_page(page)
        return 0

    # -- device steps ---------------------------------------------------
    def _launch(self, program: str, shape: tuple, **stats):
        """The span around one call of a jitted step ``program``, with
        the step's counters as stats: rows and tokens, real and padded;
        ``new_shape`` 1 when ``shape`` is new to the compile-shape audit
        (the call compiles or loads a program); and the page pool's pages
        in use and reserved, the trash page left out.  A decode launch
        also names the attention kernel's shape: ``kv_heads``,
        ``q_per_kv`` and ``head_dim``."""
        stats["new_shape"] = int(shape not in self._shapes)
        self._shapes.add(shape)
        if self.pages is not None:
            stats.update(pages_in_use=self.pages.in_use,
                         pages_reserved=self.pages.n_pages - 1)
        return span("repro.decoder.launch", program=program, **stats)

    def _sample(self, logits, picks: List[Tuple[int, tuple]]
                ) -> Dict[int, int]:
        """Greedy next tokens: ``logits`` fetched to the host (waiting
        for the device), then for each ``(rid, index)`` of ``picks`` the
        argmax of ``logits[index]``, appended to the rid's tokens."""
        with span("repro.decoder.fetch"):
            logits = np.asarray(logits)
        out: Dict[int, int] = {}
        with span("repro.decoder.sample"):
            for r, index in picks:
                nxt = int(np.argmax(logits[index]))
                self._tokens[r].append(nxt)
                out[r] = nxt
        return out

    def _decode_active(self, active: List[int]) -> Dict[int, int]:
        B = self.pool.capacity
        with span("repro.decoder.pages") as sp:
            sp.set_metadata(cow=sum(self._ensure_writable(r)
                                    for r in active))
        toks = np.full((B, 1), PAD, dtype=np.int32)
        mask = np.zeros((B,), dtype=bool)
        for r in active:
            s = self.pool.slot_of[r]
            toks[s, 0] = self._tokens[r][-1]
            mask[s] = True
        self._sync_table()
        with self._launch("decode_step", ("decode", B), rows=len(active),
                          padded_rows=B, tokens=len(active),
                          padded_tokens=B, kv_heads=self.cfg.n_kv_heads,
                          q_per_kv=self.cfg.q_per_kv,
                          head_dim=self.cfg.resolved_head_dim):
            logits, self._cache = self._decode(self.params, self._cache,
                                               toks, mask)
        return self._sample(logits, [(r, (self.pool.slot_of[r], -1))
                                     for r in active])

    def _admit(self, fresh: List[int]) -> Dict[int, int]:
        """Prefill for newly admitted rows, in one launch.  Only each
        request's unshared TAIL is prefilled, laid out one page per row
        (``_chunk_rows``), so a short tail never pads to the longest
        prompt of the admission.  Padding rows DUPLICATE row 0 (same
        tokens, same slot and base), so the duplicate scatter writes
        identical bytes and live rows stay untouched."""
        slots = [self.pool.bind(r) for r in fresh]
        with span("repro.decoder.pages"):
            bases = [self._bind_pages(r) for r in fresh]
        seqs, rows, lasts = self._chunk_rows(fresh, slots, bases)
        n_real = len(seqs)
        S = self.page_size
        Bn = _next_pow2(n_real)
        if Bn > self.pool.capacity:
            Bn = _round_up(n_real, self.pool.capacity)
        lens = [len(s) for s in seqs]
        arr = np.full((Bn, S), PAD, dtype=np.int32)
        for i, s in enumerate(seqs):
            arr[i, :lens[i]] = s
        arr[n_real:] = arr[0]
        pad = Bn - n_real
        slot_arr = np.asarray([s for s, _b in rows] + [rows[0][0]] * pad,
                              np.int32)
        base_arr = np.asarray([b for _s, b in rows] + [rows[0][1]] * pad,
                              np.int32)
        len_arr = np.asarray(lens + [lens[0]] * pad, np.int32)
        self.prefill_tokens_total += sum(lens)
        self._sync_table()
        with self._launch("prefill_into_pages",
                          ("prefill", Bn, S, self.pool.capacity),
                          rows=len(fresh), padded_rows=Bn, tokens=sum(lens),
                          padded_tokens=Bn * S, chunks=n_real):
            logits, self._cache = self._prefill_pages(
                self.params, {"tokens": arr}, self._cache, slot_arr,
                base_arr, len_arr)
        return self._sample(logits, [(r, (i, 0))
                                     for r, i in zip(fresh, lasts)])

    def _chunk_rows(self, fresh: List[int], slots: List[int],
                    bases: List[int]):
        """Each request's unshared tail cut into page-wide chunks: one row
        ``(slot, base + j*P)`` per page of ``tokens[base:]`` (``base`` is
        page-aligned, so each row writes exactly one page).  Returns the
        rows' tokens, their (slot, base), and the row of each request's
        last chunk, where its first token is sampled."""
        P = self.page_size
        seqs, rows, lasts = [], [], []
        for r, slot, base in zip(fresh, slots, bases):
            toks = self._tokens[r]
            for b in range(base, len(toks), P):
                seqs.append(toks[b:b + P])
                rows.append((slot, b))
            lasts.append(len(seqs) - 1)
        return seqs, rows, lasts

    def _grow(self, needed: int) -> None:
        """Capacity to the next power of two ≥ ``needed``; live state is
        copied across GENERICALLY — every leaf of the old cache pytree is
        prefix-sliced into the freshly initialised one (and cache keys
        the initialiser doesn't know about are carried verbatim), so
        growth is invisible to in-flight requests."""
        cap = max(self.pool.capacity, 1)
        while cap < needed:
            cap *= 2
        if cap == self.pool.capacity:
            return
        old_cap = self.pool.capacity
        old_cache = self._cache
        old_table = self._table
        new_cache = self._fresh_cache(cap)
        if old_cache is not None:
            def copy_prefix(big, small):
                if big.shape == small.shape:
                    return small
                idx = tuple(slice(0, n) for n in small.shape)
                return big.at[idx].set(small)
            merged = {}
            for key, val in new_cache.items():
                if key in old_cache:
                    merged[key] = jax.tree_util.tree_map(
                        copy_prefix, val, old_cache[key])
                else:
                    merged[key] = val
            for key, val in old_cache.items():    # keys init doesn't know
                merged.setdefault(key, val)
            new_cache = merged
        self._cache = new_cache
        self.pages.grow(1 + cap * self.max_pages)
        if old_table is not None:
            self._table[:old_cap] = old_table
        self._table_dirty = True
        self.pool.grow(cap)
        self._sync_table()

    def _step_full(self, rids: List[int]) -> Dict[int, int]:
        """Reference path: full forward over prompt+generated each step."""
        seqs = [self._tokens[r] for r in rids]
        lens = [len(s) for s in seqs]
        B = _next_pow2(len(rids))
        S = _round_up(max(lens), 8)
        arr = np.full((B, S), PAD, dtype=np.int32)
        for i, s in enumerate(seqs):
            arr[i, :len(s)] = s
        with self._launch("forward", ("full", B, S), rows=len(rids),
                          padded_rows=B, tokens=sum(lens),
                          padded_tokens=B * S):
            logits = self._fwd(self.params, arr)
        return self._sample(logits, [(r, (i, lens[i] - 1))
                                     for i, r in enumerate(rids)])

    @property
    def shape_buckets(self) -> int:
        """Distinct compiled shapes seen — an upper bound on recompiles.
        O(1) in decode steps for the slot path (decode compiles once per
        pool capacity; prefill once per admission bucket)."""
        return len(self._shapes)


def make_pff_step_fn(prompt_len: int = PROMPT_LEN, *,
                     slot_cached: bool = True,
                     max_len: Optional[int] = None):
    """Step function for :class:`~repro.cluster.LiveExecutor.step_fns`.

    Lazily builds a :class:`StreamingDecoder` inside the library's
    payloads (it belongs to the hosted context: it dies with a spill and
    is rebuilt on re-materialisation) and advances the current members by
    one token.  Request payloads are the claims to verify.

    Requests the scheduler pulled OUT of the batch mid-flight (requeued
    on preemption / migrated to another replica) are detected by their
    absence from ``members`` and their decoder state — slot, pages,
    token buffers — is freed immediately; previously these rows leaked
    until the decoder was torn down.

    The returned function carries a ``prefill`` attribute — the
    disaggregation entry the live executor uses to run a request's
    PREFILL phase without joining a stream (see
    :meth:`repro.cluster.LiveExecutor._run_prefill`)."""
    def _decoder(payloads) -> StreamingDecoder:
        dec = payloads.get("_stream_decoder")
        if dec is None:
            engine = payloads["xla_executable"]
            ci = payloads["context_inputs"]
            dec = StreamingDecoder(engine.cfg, engine.params,
                                   ci["tokenizer"], ci["template"],
                                   prompt_len=prompt_len,
                                   slot_cached=slot_cached, max_len=max_len)
            payloads["_stream_decoder"] = dec
        # shipped-in KV snapshots parked before this decoder existed (or
        # between steps): take ownership so has_suspended resumes them
        inbox = payloads.pop("_kv_inbox", None)
        if inbox:
            for rid, snap in inbox.items():
                dec.adopt(rid, snap)
        return dec

    def step_fn(payloads, members):
        with span("repro.decoder.step", rows=len(members)):
            with span("repro.decoder.membership"):
                dec = _decoder(payloads)
                present = {r.request_id for r in members}
                for rid in dec.active_rids():
                    if rid not in present:        # requeued away mid-batch
                        dec.finish(rid)
                for r in members:
                    # a preempted member coming back: restore its KV
                    # snapshot in place of the admission prefill (suspend
                    # removed it from active_rids, so the cleanup above
                    # never touches it)
                    if dec.has_suspended(r.request_id):
                        dec.resume(r.request_id)
                for r in members:
                    dec.ensure(r.request_id, r.payload)
                    if dec.truncated.get(r.request_id):
                        r.truncated = True
            out = dec.step([r.request_id for r in members])
            with span("repro.decoder.membership"):
                for r in members:
                    if r.steps_done + 1 >= r.n_units:  # last step: free
                        dec.finish(r.request_id)
        return out

    def prefill(payloads, request) -> Tuple[int, List[int]]:
        """Run ``request``'s PREFILL phase: admit it, emit the first
        ``prompt_units`` tokens exactly as the colocated steps would,
        then suspend the row — the host snapshot IS the shippable KV.
        Returns ``(snapshot_nbytes, tokens)``; the DECODE phase resumes
        from the snapshot (same worker or shipped) and continues the
        token stream bit-exactly."""
        dec = _decoder(payloads)
        rid = request.request_id
        dec.ensure(rid, request.payload)
        if dec.truncated.get(rid):
            request.truncated = True
        toks: List[int] = []
        for _ in range(max(int(request.prompt_units), 1)):
            toks.append(dec.step([rid])[rid])
        return dec.suspend(rid), toks

    step_fn.prefill = prefill
    return step_fn


def stream_verdict(tokenizer, step_tokens: Iterable[int]) -> str:
    """Decode one request's accumulated step outputs into a verdict."""
    return parse_verdict(tokenizer.decode(list(step_tokens)))

"""The library process: materialises a context once, serves invocations.

Paper §5.2: the worker fork-execs a *library* process per context recipe.
The library stages the recipe's elements into the worker cache, executes the
context code (model load → host → device), keeps the resulting state in its
address space, and then executes every subsequent invocation of the bound
function directly against that state — so initialisation is paid once per
worker, not once per task.

This class is backend-neutral: in *sim* mode :meth:`materialize_cost`
returns the staging time from the hardware model and ``payloads`` stays
empty; in *live* mode :meth:`materialize` actually runs each element's
``loader`` (device_put, jit compile, ...) and :meth:`invoke` calls the
bound function.
"""
from __future__ import annotations

import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set

from .cache import ContextCache
from .context import (ContextRecipe, MAX_BATCH_SLOTS, MaterializedContext,
                      Tier)


@dataclass
class StagingCost:
    """Seconds spent per staging phase of one materialisation.

    ``fetch_bytes`` counts the bytes the fetch phase actually moved over
    the network — the context plane compares it against the bytes its
    :class:`~repro.core.plane.PlacementPlan` priced for the same op, so
    plan/executed byte accounting can be asserted equal.
    """
    fetch_s: float = 0.0      # network/shared-fs → local disk
    load_s: float = 0.0       # disk → host memory (deserialise)
    device_s: float = 0.0     # host → accelerator
    activation_s: float = 0.0  # fork-exec + import
    fetch_bytes: int = 0      # bytes moved over the network by the fetch

    @property
    def total_s(self) -> float:
        return self.fetch_s + self.load_s + self.device_s + self.activation_s


class Library:
    """One hosted context on one worker.

    With multi-context workers several libraries are concurrently resident;
    a library that loses the device/host capacity race is *spilled* — its
    elements demoted to local disk and its pins released — rather than torn
    down, so re-hosting pays load+device but never the network fetch.

    A library also owns a *dynamic batch*: the set of admitted requests it
    decodes together.  Membership changes between steps — :meth:`admit`
    adds a request (it starts stepping at the next step boundary, via
    :meth:`activate`), :meth:`step` advances every active member by one
    decode step and returns the ones that hit their budget, and
    :meth:`drain` removes the unfinished members (eviction / shutdown).
    The slot budget is a function of the hosting device's free memory, so
    the same request stream batches differently across a heterogeneous
    pool.
    """

    def __init__(self, recipe: ContextRecipe, cache: ContextCache):
        self.recipe = recipe
        self.cache = cache
        self.context = MaterializedContext(recipe)
        self.ready = False
        self.invocations = 0
        self.spills = 0
        # continuous-batching state: request_id -> request
        self.batch: "OrderedDict[int, Any]" = OrderedDict()
        self.joining: Set[int] = set()      # admitted, start at next boundary

    # ------------------------------------------------------------------
    # Continuous batching: the admission interface
    # ------------------------------------------------------------------
    def slot_budget(self, device_bytes: int, active_params: float) -> int:
        """How many requests this library may decode concurrently here.

        Derived from the hardware catalog: device memory left after the
        recipe's resident bytes, divided by the per-request decode-state
        footprint, clamped to [1, MAX_BATCH_SLOTS].  In live mode the
        footprint is the MEASURED per-slot cache bytes once the executor
        has fed one back (see ``ContextRecipe.record_slot_bytes``); the
        analytic ``KV_BYTES_PER_PARAM`` estimate only seeds the first
        admission.  The measured figure is the decoder's worst-case
        per-request page allotment (``max_pages * page_bytes``)
        — shared-prefix pages are refcounted device-side, so the budget
        is conservative and admission arithmetic stays unchanged."""
        free = device_bytes - self.recipe.nbytes(Tier.DEVICE)
        per_slot = self.recipe.decode_slot_bytes(active_params)
        return max(1, min(MAX_BATCH_SLOTS, free // per_slot))

    def admit(self, request, budget: int) -> bool:
        """Add ``request`` to the dynamic batch if a slot is free.  The
        request starts stepping at the next boundary (:meth:`activate`)."""
        if len(self.batch) >= budget:
            return False
        self.batch[request.request_id] = request
        self.joining.add(request.request_id)
        return True

    def activate(self, only: Optional[Set[int]] = None) -> List[Any]:
        """Boundary reached: newly admitted members begin stepping.

        ``only`` restricts activation to a subset of the joining ids —
        the sim runner uses it so a request admitted at time t can never
        be activated at an earlier (lazily settled) boundary."""
        rids = self.joining if only is None else \
            (self.joining & set(only))
        started = [self.batch[rid] for rid in rids if rid in self.batch]
        self.joining -= set(rids)
        return started

    def step(self) -> List[Any]:
        """Advance every ACTIVE member one decode step; pop & return the
        requests that completed their unit budget."""
        finished = []
        for rid, req in list(self.batch.items()):
            if rid in self.joining:
                continue
            req.steps_done += 1
            if req.steps_done >= req.n_units:
                del self.batch[rid]
                finished.append(req)
        return finished

    def drain(self) -> List[Any]:
        """Remove every unfinished member (eviction / spill / teardown)."""
        out = list(self.batch.values())
        self.batch.clear()
        self.joining.clear()
        return out

    @property
    def stepping(self) -> int:
        """Members actually decoding (admitted minus still-joining)."""
        return len(self.batch) - len(self.joining)

    # ------------------------------------------------------------------
    # Sim path: compute cost, update the cache accounting
    # ------------------------------------------------------------------
    def materialize_cost(self, hw, *, already_local: bool = False,
                         fetch_bw: Optional[float] = None) -> StagingCost:
        """Staging cost on hardware ``hw`` given current cache residency.

        ``hw`` provides: ``disk_bw``, ``h2d_bw`` (bytes/s), and
        ``compile_s(recipe)``.  ``fetch_bw`` is the network path (shared fs
        or peer transfer) used for elements not yet on local disk; when
        ``already_local`` the fetch phase is skipped entirely.
        """
        cost = StagingCost(activation_s=self.recipe.activation_s)
        for e in self.recipe.elements:
            tier = self.cache.lookup(e.key)
            home = e.home
            if tier is None and not already_local:
                bw = fetch_bw or hw.disk_bw
                cost.fetch_s += e.nbytes_disk / bw
                cost.fetch_bytes += e.nbytes_disk
                tier = Tier.DISK
            elif tier is None:
                tier = Tier.DISK
            if tier.order < Tier.HOST.order <= home.order:
                cost.load_s += e.nbytes(Tier.HOST) / hw.disk_bw
                tier = Tier.HOST
            if tier.order < Tier.DEVICE.order <= home.order:
                if e.name == "xla_executable":
                    cost.device_s += hw.compile_s(self.recipe)
                else:
                    cost.device_s += e.nbytes(Tier.DEVICE) / hw.h2d_bw
                tier = Tier.DEVICE
            self.cache.put(e, tier, pinned=True)
            self.context.tiers[e.name] = tier
        self.ready = True
        return cost

    # ------------------------------------------------------------------
    # Live path: actually run the loaders
    # ------------------------------------------------------------------
    def materialize(self) -> StagingCost:
        """Run every element's loader; returns measured wall-time cost."""
        cost = StagingCost()
        for e in self.recipe.elements:
            tier = self.cache.tier_of(e.key)
            home = Tier.DEVICE if e.nbytes_device else Tier.HOST
            if tier is not None and tier.order >= home.order and \
                    e.name in self.context.payloads:
                self.context.tiers[e.name] = tier
                continue
            t0 = time.perf_counter()
            if e.loader is not None:
                self.context.payloads[e.name] = e.loader()
            dt = time.perf_counter() - t0
            if e.name == "deps":
                cost.activation_s += dt
            elif home is Tier.DEVICE:
                cost.device_s += dt
            else:
                cost.load_s += dt
            self.cache.put(e, home, pinned=True)
            self.context.tiers[e.name] = home
        self.ready = True
        return cost

    def invoke(self, fn: Callable[..., Any], *args, **kw) -> Any:
        """Execute an invocation inside this library's address space."""
        assert self.ready, "library not materialised"
        self.invocations += 1
        return fn(self.context.payloads, *args, **kw)

    def spill(self, to: Tier = Tier.DISK) -> None:
        """Demote this library's residency to ``to`` (default: local disk).

        Releases this library's pin on every element; an element is only
        demoted once its pin count hits zero, so elements shared with other
        resident libraries (the deps package, typically) stay put.  The
        byte accounting moves with the demotion: DEVICE and HOST bytes are
        freed, the DISK staging copy survives (unpinned — evictable under
        disk pressure), and re-hosting pays load+device but not fetch.
        """
        if not self.ready:
            return
        self.drain()                # callers gate on an empty batch
        for e in self.recipe.elements:
            try:
                self.cache.pin(e.key, False)
            except KeyError:
                continue
            if self.cache.pins(e.key) == 0:
                self.cache.demote(e.key, to)
            t = self.cache.tier_of(e.key)
            if t is not None:
                self.context.tiers[e.name] = t
        self.context.payloads.clear()
        self.ready = False
        self.spills += 1

    def teardown(self) -> None:
        self.drain()
        for e in self.recipe.elements:
            try:
                self.cache.pin(e.key, False)
            except KeyError:
                pass
        self.context.payloads.clear()
        self.ready = False

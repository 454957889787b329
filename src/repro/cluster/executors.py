"""Request-execution backends: discrete-event simulation and live JAX.

Both executors drive the SAME :class:`~repro.cluster.scheduler.Scheduler`
(routing, registry, cache, policies).  Only the source of time differs:

* :class:`SimExecutor` — durations from the calibrated hardware catalog
  (paper-scale runs: 150 k inferences, 186 GPUs).  Stream batches advance
  with a per-step event model: each step of a size-B dynamic batch costs
  ``device.step_time(active_params, B)``, membership changes between
  steps, and a batch fast-forwards in O(membership changes) events rather
  than O(steps);
* :class:`LiveExecutor` — really materialises contexts (device_put, jit)
  and runs forward passes on this container's device, measuring wall
  time.  Stream batches are advanced one decode step at a time through a
  per-recipe ``step_fn``; the decode state lives in a persistent device
  slot pool (see :mod:`repro.inference.streaming`) so membership churn
  costs one admission prefill per joiner — never a re-prefill of rows
  already in flight — and each step is O(1) in prefix length.

Deprecated exclusive tasks (``Task`` / ``submit_sweep``) keep the
pre-redesign run-to-completion path in both backends, which is also the
benchmark baseline continuous admission is measured against.
"""
from __future__ import annotations

import random
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core import (ContextMode, NAIVE, OpKind, PARTIAL, PERVASIVE,
                    PlacementPlan, PlanOp, Tier, WarmPoolPolicy)
from ..tracing import span
from .events import EventLoop
from .hardware import ClusterSpec
from .scheduler import Assignment, PREFILL, Scheduler
from .worker import Worker

_EPS = 1e-9


def _kv_nbytes(tree) -> int:
    """Byte size of a host-side KV snapshot pytree (no jax dependency —
    the sim backend must stay importable without an accelerator stack)."""
    if hasattr(tree, "nbytes"):
        return int(tree.nbytes)
    if isinstance(tree, dict):
        return sum(_kv_nbytes(v) for v in tree.values())
    if isinstance(tree, (list, tuple)):
        return sum(_kv_nbytes(v) for v in tree)
    return 0


class _PlanOpExecution:
    """The ONE plan-op execution path both executors share.

    The context plane compiles intents into :class:`PlacementPlan` ops;
    this mixin walks the ops, makes worker-side room (authoritative
    spills), and feeds the op lifecycle back to the plane.  Only
    :meth:`_materialize_op` differs per backend — the sim charges the
    calibrated staging cost on the event loop, live mode really runs the
    loaders — which is exactly the dual-backend discipline the scheduler
    already follows.
    """

    def execute_plan(self, plan: PlacementPlan) -> None:
        plane = self.sched.plane
        for op in plan.ops:
            if op.kind in (OpKind.FETCH, OpKind.PEER_COPY, OpKind.PROMOTE):
                self._execute_acquire_op(op)
            elif op.kind is OpKind.SPILL:
                # both a Release compilation's demotion and an acquire
                # op's preview; executing the preview up front is what
                # make_room would do anyway (and make_room still backstops
                # any spill the preview missed)
                self._execute_spill_op(op)
            elif op.kind is OpKind.EVICT:
                plane.note_released(op.recipe_key, op.worker_id)

    def _execute_spill_op(self, op: PlanOp) -> None:
        sched = self.sched
        w = sched.workers.get(op.worker_id)
        if w is None:
            return
        lib = w.libraries.get(op.recipe_key)
        if lib is None or not lib.ready \
                or w.running_by_recipe.get(op.recipe_key, 0) > 0:
            return                      # gone, already spilled, or busy
        lib.spill()
        sched.plane.note_spilled(op.recipe_key, op.worker_id)
        sched.spilled_libraries += 1

    def _execute_acquire_op(self, op: PlanOp) -> None:
        sched = self.sched
        plane = sched.plane
        w = sched.workers.get(op.worker_id)
        if w is None or not w.idle or w.has_ready(op.recipe_key):
            plane.op_aborted(op)        # pool moved under the plan
            return
        recipe = plane.registry.recipes[op.recipe_key]
        for k in w.make_room(recipe):
            plane.note_spilled(k, w.worker_id)
            sched.spilled_libraries += 1
        w.staging = True
        plane.op_started(op)
        self._materialize_op(op, w, recipe)

    def _materialize_op(self, op: PlanOp, w: Worker, recipe,
                        attempt: int = 0) -> None:
        raise NotImplementedError


class _StreamRun:
    """Sim-side driver for ONE library's dynamic batch on one worker.

    Keeps the step clock: ``t_boundary`` is the last step boundary,
    ``step_s`` the current per-step cost (a function of batch size).
    Progress is settled lazily — the runner schedules a single event at
    the next *interesting* boundary (earliest member completion, or the
    first boundary after an admission) and bulk-advances whole segments
    of stable membership, so a 256-step request with no churn costs one
    event, not 256.
    """

    def __init__(self, ex: "SimExecutor", a: Assignment):
        self.ex = ex
        self.w = a.worker
        self.key = a.request.recipe_key
        self.lib = a.worker.libraries[self.key]
        self.active_params = a.request.active_params
        self.assign: Dict[int, Assignment] = {a.request.request_id: a}
        self.join_t: Dict[int, float] = {}   # admission wall time per rid
        self.t_boundary = 0.0
        self.step_s = 0.0
        self.begun = False
        self._timer = None
        # steps_done at each member's last checkpoint ATTEMPT (landed or
        # budget-deferred) — the cadence counter for ckpt_every_steps
        self._ckpt_mark: Dict[int, int] = {}

    # -- lifecycle ------------------------------------------------------
    def alive(self) -> bool:
        """False once the worker was evicted or this run was replaced;
        also lazily unregisters a dead run (eviction never notifies the
        executor, so the stale entry would otherwise leak)."""
        sched = self.ex.sched
        ok = (sched.workers.get(self.w.worker_id) is self.w and
              self.ex._streams.get((self.w.worker_id, self.key)) is self)
        if not ok and self.ex._streams.get(
                (self.w.worker_id, self.key)) is self:
            del self.ex._streams[(self.w.worker_id, self.key)]
        return ok

    def admit(self, a: Assignment) -> None:
        """A request joined (scheduler already put it in ``lib.batch``);
        it starts stepping at the first boundary at/after NOW — never at
        an earlier, lazily settled one."""
        if not self.alive():
            return                      # worker evicted mid-dispatch
        rid = a.request.request_id
        self.assign[rid] = a
        self.join_t[rid] = self.ex.loop.now
        if self.begun:
            self.settle(self.ex.loop.now)
            self.schedule()

    def begin(self) -> None:
        """Staging done (or warm): the batch starts decoding now."""
        if not self.alive():
            return
        self.begun = True
        self.t_boundary = self.ex.loop.now
        self.lib.activate()
        self.join_t.clear()
        self._reprice()
        self.schedule()

    def _reprice(self) -> None:
        # price by the members actually decoding (joiners waiting for
        # their boundary don't occupy the step yet)
        self.step_s = self.w.device.step_time(
            self.active_params, max(self.lib.stepping, 1))

    # -- event plumbing -------------------------------------------------
    def schedule(self) -> None:
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self.w.frozen_s is not None:
            return              # crashed/hung: no future step completes;
                                # the FailureDetector's eviction requeues
        if not self.lib.batch:
            self.close()
            return
        if self.lib.stepping == 0:
            # everyone left is a joiner (preemption can suspend the last
            # settled member): there is no running step whose boundary a
            # due joiner could wait for — activate the due ones NOW, and
            # if none are due yet the in-flight admit() will reschedule.
            due = self._due_joiners(self.ex.loop.now)
            if not due:
                return
            self.t_boundary = self.ex.loop.now
            self.lib.activate(due)
            for rid in due:
                self.join_t.pop(rid, None)
            self._reprice()
        if self.lib.joining:
            t_next = self.t_boundary + self.step_s
        else:
            min_rem = min(r.n_units - r.steps_done
                          for r in self.lib.batch.values())
            t_next = self.t_boundary + min_rem * self.step_s
        self._timer = self.ex.loop.at(max(t_next, self.ex.loop.now),
                                      self._fire)

    def _due_joiners(self, boundary: float) -> list:
        """Joining members whose admission happened at/before
        ``boundary`` — the only ones allowed to activate there.  A
        member the scheduler admitted but whose dispatch the manager has
        not finished (admit() not yet called) is never due."""
        return [rid for rid in self.lib.joining
                if self.join_t.get(rid, float("inf")) <= boundary + _EPS]

    def _fire(self) -> None:
        self._timer = None
        if not self.alive():
            return
        self.settle(self.ex.loop.now)
        self.schedule()
        self.ex.pump()

    def close(self) -> None:
        self.ex._streams.pop((self.w.worker_id, self.key), None)
        self.ex.sched.close_stream(self.w.worker_id, self.key)

    # -- the step clock -------------------------------------------------
    def settle(self, t: float) -> None:
        """Advance the batch to time ``t``: whole segments of stable
        membership at once, completing members and absorbing DUE joiners
        at the boundaries in between.  A joiner is due only at
        boundaries at/after its admission time — lazily settled PAST
        boundaries must never retro-activate it (it would be credited
        with steps it never ran).

        With ``Scheduler.ckpt_every_steps`` set, segments are ALSO
        clamped at each member's next checkpoint-cadence boundary, where
        the member's KV snapshot is exported to another failure zone as
        a KV_CKPT plane op — keeping the event count O(membership
        changes + checkpoints), never O(steps).  A frozen (crashed or
        hung) worker settles only up to the instant it died: a dead GPU
        completes nothing, however late the detector notices."""
        fz = self.w.frozen_s
        if fz is not None:
            t = min(t, fz)
        every = self.ex.sched.ckpt_every_steps
        while self.lib.stepping > 0 and self.step_s > 0:
            span = (t - self.t_boundary) + _EPS
            if span < self.step_s:
                break
            k = int(span / self.step_s)
            min_rem = min(r.n_units - r.steps_done
                          for rid, r in self.lib.batch.items()
                          if rid not in self.lib.joining)
            if self._due_joiners(self.t_boundary + self.step_s):
                k = 1                 # membership changes next boundary
            k = max(1, min(k, min_rem))
            if every:
                to_ckpt = min(
                    every - (r.steps_done - self._ckpt_mark.setdefault(
                        rid, r.steps_done))
                    for rid, r in self.lib.batch.items()
                    if rid not in self.lib.joining)
                k = max(1, min(k, to_ckpt))
            stepping = [r for rid, r in self.lib.batch.items()
                        if rid not in self.lib.joining]
            t_seg0 = self.t_boundary
            self.t_boundary = t_seg0 + k * self.step_s
            for _ in range(k - 1):    # quiet steps: nobody can finish
                self.lib.step()
            finished = self.lib.step()
            for r in stepping:
                if r.t_first_step is None:
                    r.t_first_step = t_seg0 + self.step_s
            for r in finished:
                self._ckpt_mark.pop(r.request_id, None)
                # a finished request needs no checkpoint: refund any
                # still-in-flight one so drained runs meter to parity
                self.ex.sched.plane.kv_ckpt_aborted(r.request_id,
                                                    self.t_boundary)
                a = self.assign.pop(r.request_id, None)
                if a is not None:
                    self.ex.sched.on_complete(a, a.t_dispatch,
                                              self.t_boundary,
                                              t_first_step=r.t_first_step)
            if every:
                for rid, r in list(self.lib.batch.items()):
                    if rid in self.lib.joining:
                        continue
                    mark = self._ckpt_mark.setdefault(rid, r.steps_done)
                    if r.steps_done - mark >= every:
                        self._ckpt_mark[rid] = r.steps_done
                        self.ex._fire_ckpt(self, r, self.t_boundary)
            due = self._due_joiners(self.t_boundary)
            if due:                   # joiners enter at this boundary
                self.lib.activate(due)
                for rid in due:
                    self.join_t.pop(rid, None)
            self._reprice()


class SimExecutor(_PlanOpExecution):
    """Discrete-event executor with the calibrated cluster time model.

    ``prestage=True`` enables proactive spanning-tree context distribution
    (paper §5.3.1): when workers join and a context already has ready
    hosts, the scheduler plans a fanout-capped tree over the joiners and
    stages them immediately, instead of lazily on first task dispatch.

    ``warm_pool`` plugs in a :class:`~repro.core.WarmPoolPolicy`: after
    each dispatch round, hot recipes are replicated onto leftover idle
    capable workers ahead of demand, so the stream's next requests route
    warm.
    """

    def __init__(self, scheduler: Scheduler, loop: Optional[EventLoop] = None,
                 *, prestage: bool = False, fanout_cap: int = 3,
                 warm_pool: Optional[WarmPoolPolicy] = None,
                 retry_seed: int = 0):
        self.sched = scheduler
        self.loop = loop or EventLoop()
        scheduler.clock = lambda: self.loop.now
        self.cluster: ClusterSpec = scheduler.cluster
        self.prestage_enabled = prestage
        self.fanout_cap = fanout_cap
        self.warm_pool = warm_pool
        self._manager_free = 0.0
        self._fs_streams = 0
        self._peer_streams: Dict[str, int] = {}   # outbound per source
        self._streams: Dict[Tuple[str, str], _StreamRun] = {}
        # transfer retry-with-backoff (docs/failure-model.md): an acquire
        # op whose SOURCE died (or a FaultInjector transfer fault hit) is
        # aborted-refunded and retried against an alternate source under
        # capped exponential backoff with seeded jitter
        self.retry_base_s = 0.5
        self.retry_cap_s = 30.0
        self.retry_jitter = 0.25
        self._retry_rng = random.Random(retry_seed)
        self._failed_transfers: set = set()   # (recipe_key, dst_worker)
        self.transfer_retries = 0
        self._ckpt_rr = 0               # round-robin ckpt-host cursor
        self._budget_retry = None       # pending deferred-replication timer
        self._prestage_retry = None     # deferred prestage-edge timer
        self._prestage_pending: set = set()   # recipes with deferred edges
        self._deadline_timer = None     # next gateway deadline expiry
        # arrivals scheduled on the loop but not yet submitted
        # (Application.submit_stream); keeps run() from stopping early
        self.pending_arrivals = 0
        # demand-driven supply: an elastic Factory installs its step()
        # here so the pool re-sizes on every pump, not just on its tick
        self.supply_hook: Optional[Callable[[], None]] = None

    # -- proactive spanning-tree distribution (§5.3.1) ---------------------
    def prestage(self, recipe_key: str) -> int:
        """Stage ``recipe_key`` onto every context-less idle worker via a
        topology-aware spanning tree. Returns the number of targets.

        BUDGET-AWARE: each cross-zone tree edge is admission-checked
        against the plane's :class:`LinkBudget` as a ``PEER_COPY`` op, so
        operators capping DCN bytes cap the bulk distribution too — not
        just the warm pool's share.  A deferred edge re-emits next round
        exactly like a deferred ``Replicate``: its subtree is skipped
        (children cannot source from a copy that never landed), the
        deferral is counted, and a half-window timer re-runs prestage for
        the recipe once the budget window can have slid."""
        from ..core import Peer, plan_spanning_tree
        reg = self.sched.registry
        recipe = reg.recipes[recipe_key]
        ready = reg.ready_workers(recipe_key)
        if not ready:
            return 0
        have = reg.workers_with(recipe_key)
        c = self.cluster
        mk = lambda w: Peer(w.worker_id, w.zone, bw_local=c.peer_bw_local,
                            bw_cross=c.peer_bw_cross)
        sources = [mk(self.sched.workers[wid]) for wid in ready
                   if wid in self.sched.workers]
        targets = [mk(w) for w in self.sched.workers.values()
                   if w.worker_id not in have and w.idle
                   and w.can_host(recipe)]
        if not targets or not sources:
            return 0
        plane = self.sched.plane
        plan = plan_spanning_tree(recipe.transfer_bytes, sources, targets,
                                  fanout_cap=self.fanout_cap,
                                  t0=self.loop.now)
        zones = {w.worker_id: w.zone for w in self.sched.workers.values()}
        dead: set = set()               # dsts whose edge the budget deferred
        deferred = 0
        for edge in plan.edges:
            w = self.sched.workers.get(edge.dst)
            if w is None:
                continue
            if edge.src in dead:
                # parent edge deferred: this copy has no source yet; the
                # retry round re-plans the tree from what actually landed
                dead.add(edge.dst)
                deferred += 1
                continue
            op = PlanOp(OpKind.PEER_COPY, recipe_key, edge.dst,
                        nbytes=recipe.transfer_bytes, src_worker=edge.src,
                        src_zone=zones.get(edge.src, w.zone),
                        dst_zone=w.zone)
            if not plane.budget.admits(op, self.loop.now):
                dead.add(edge.dst)
                deferred += 1
                continue
            plane.budget.charge(op, self.loop.now)
            w.staging = True
            plane.note_staging(recipe_key, edge.dst)

            def arrive(wid=edge.dst, src=edge.src):
                w = self.sched.workers.get(wid)
                if w is None or w.frozen_s is not None:
                    return                      # evicted while in flight
                for k in w.make_room(recipe):
                    plane.note_spilled(k, wid)
                    self.sched.spilled_libraries += 1
                lib = w.library_for(recipe)
                cost = lib.materialize_cost(w.device, already_local=False,
                                            fetch_bw=float("inf"))
                # the tree edge's bytes landed: meter them per zone pair
                plane.record_transfer(recipe_key, zones.get(src, w.zone),
                                      w.zone, cost.fetch_bytes)

                def ready_cb(wid=wid):
                    w = self.sched.workers.get(wid)
                    if w is None or w.frozen_s is not None:
                        return
                    w.staging = False
                    plane.note_ready(recipe_key, wid)
                    self.pump()

                self.loop.after(cost.total_s, ready_cb)

            self.loop.at(edge.end_s, arrive)
        if deferred:
            plane.deferred_intents += deferred
            self._prestage_pending.add(recipe_key)
            if self._prestage_retry is None:
                def retry():
                    self._prestage_retry = None
                    pending, self._prestage_pending = \
                        self._prestage_pending, set()
                    for key in sorted(pending):
                        if key in self.sched.registry.recipes:
                            self.prestage(key)
                    self.pump()
                self._prestage_retry = self.loop.after(
                    plane.budget.window_s / 2, retry)
        return len(targets) - deferred

    # -- warm-pool replication (demand-driven, beyond prestage) ------------
    def _apply_warm_pool(self) -> int:
        """Compile Replicate intents (recovery + policy) through the
        context plane and execute the budget-admitted ops.  Intents the
        budget window deferred are retried — not dropped — once the
        window can have slid, even if no other event re-pumps first."""
        if self.warm_pool is None:
            return 0
        plane = self.sched.plane
        view = self.sched.view(now=self.loop.now)
        intents = list(plane.recovery_intents(view))
        intents += self.warm_pool.intents(view)
        if not intents:
            return 0
        plan = plane.compile(intents, view)
        plane.commit(plan, now=view.now)
        self.execute_plan(plan)
        if any(d.retriable for d in plan.deferred) \
                and self._budget_retry is None:
            def retry():
                self._budget_retry = None
                self.pump()
            self._budget_retry = self.loop.after(
                plane.budget.window_s / 2, retry)
        return len(plan.acquire_ops())

    # -- shared plan-op path: the sim's staging-time backend ---------------
    def _materialize_op(self, op, w: Worker, recipe,
                        attempt: int = 0) -> None:
        lib = w.library_for(recipe)
        if op.kind is OpKind.PROMOTE:
            fetch_bw = None                     # promotion only, no fetch
        elif op.kind is OpKind.PEER_COPY:
            base = (self.cluster.peer_bw_cross if op.cross_zone
                    else self.cluster.peer_bw_local)
            fetch_bw = base / (self._peer_streams.get(op.src_worker, 0) + 1)
        else:                                   # FETCH via the shared fs
            fetch_bw = self._fs_bw()
        cost = lib.materialize_cost(w.device, fetch_bw=fetch_bw)
        if cost.fetch_s > 0:
            if op.kind is OpKind.PEER_COPY:
                self._take_peer_stream(op.src_worker, cost.fetch_s)
            else:
                self._with_fs_stream(cost.fetch_s)

        def ready_cb(wid=op.worker_id):
            w = self.sched.workers.get(wid)
            if w is None:
                return                          # evicted: plane refunded
            src = op.src_worker
            src_w = self.sched.workers.get(src) if src is not None else None
            failed = (op.recipe_key, wid) in self._failed_transfers
            self._failed_transfers.discard((op.recipe_key, wid))
            if failed or (src is not None and
                          (src_w is None or src_w.frozen_s is not None)):
                # the source died (or a transfer fault hit) mid-flight:
                # abort-refund the op, then retry against an alternate
                # source under capped backoff (never silently complete a
                # copy whose bytes had no live origin)
                self.sched.plane.op_aborted(op, self.loop.now)
                self.transfer_retries += 1
                self._retry_acquire(op.recipe_key, wid, recipe, attempt)
                return
            if w.frozen_s is not None:
                return          # dest crashed silently: the detector's
                                # eviction will refund this op
            w.staging = False
            self.sched.plane.op_completed(op, moved_bytes=cost.fetch_bytes)
            self.pump()

        self.loop.after(cost.total_s, ready_cb)

    def _retry_acquire(self, key: str, wid: str, recipe,
                       attempt: int) -> None:
        """Re-attempt a failed acquire on ``wid`` after capped
        exponential backoff with seeded jitter, against whatever source
        the plane picks NOW (the dead one is tombstoned, so an alternate
        ready peer or the shared fs wins)."""
        delay = min(self.retry_base_s * (2 ** attempt), self.retry_cap_s)
        delay *= 1.0 + self.retry_jitter * self._retry_rng.random()

        def again():
            sched = self.sched
            w = sched.workers.get(wid)
            if w is None or w.frozen_s is not None:
                return                  # dest gone meanwhile
            if w.has_ready(key):
                return                  # another path already staged it
            plane = sched.plane
            view = sched.view(now=self.loop.now)
            src = plane._pick_source(key, w, view)
            nbytes = view.missing_bytes(w, recipe)
            if src is None:
                op = PlanOp(OpKind.FETCH, key, wid, nbytes=nbytes,
                            dst_zone=w.zone)
            else:
                op = PlanOp(OpKind.PEER_COPY, key, wid, nbytes=nbytes,
                            src_worker=src.worker_id, src_zone=src.zone,
                            dst_zone=w.zone)
            plane.commit(PlacementPlan(ops=[op]), now=self.loop.now)
            plane.op_started(op)
            self._materialize_op(op, w, recipe, attempt=attempt + 1)

        self.loop.after(delay, again)

    def fail_transfer(self, recipe_key: str, dst_worker: str) -> None:
        """Mark the in-flight transfer for ``(recipe_key, dst_worker)``
        as failed: its completion event aborts-refunds and retries with
        backoff instead of landing (the FaultInjector's transfer
        fault)."""
        self._failed_transfers.add((recipe_key, dst_worker))

    # -- crash safety: periodic KV checkpoint export -----------------------
    def _ckpt_target(self, req, src: Worker) -> Optional[Worker]:
        """A checkpoint host for ``req``: a live worker with the recipe
        warm, preferring a DIFFERENT failure zone than the decode worker
        (a zone-correlated storm must not take both copies)."""
        sched = self.sched
        ready = sched.registry.ready_workers(req.recipe_key)
        # creation order, not lexical: worker ids come from a
        # process-global counter, so lexical order (or anything keyed on
        # raw id/request numbers) would make placement depend on how
        # many workers unrelated runs in this process created first
        cands = [sched.workers[wid]
                 for wid in sorted(ready, key=lambda i: (len(i), i))
                 if wid != src.worker_id and wid in sched.workers
                 and sched.workers[wid].frozen_s is None]
        if not cands:
            return None
        other_zone = [w for w in cands if w.zone != src.zone]
        pool = other_zone or cands
        # sticky while eligible: each landed snapshot then supersedes
        # the previous one in place on the same host
        for w in pool:
            if w.worker_id == req.ckpt_worker:
                return w
        self._ckpt_rr += 1
        return pool[self._ckpt_rr % len(pool)]

    def _fire_ckpt(self, run: _StreamRun, req, t: float) -> None:
        """Export one settled member's KV snapshot to a checkpoint host:
        price it as a KV_CKPT plane op, admission-check the budget
        window (a checkpoint the window cannot absorb is DEFERRED to the
        next cadence boundary, never dropped), occupy an outbound peer
        stream for the transfer, and record the landed checkpoint on the
        request.  Stale-safe: an eviction of either endpoint aborts the
        in-flight op and the landed event becomes a no-op."""
        sched = self.sched
        plane = sched.plane
        w = run.w
        rid = req.request_id
        if rid in plane._inflight_ckpts:
            return                  # previous snapshot still in transit
        dst = self._ckpt_target(req, w)
        if dst is None:
            sched.kv_ckpts_deferred += 1
            return
        recipe = sched.registry.recipes[req.recipe_key]
        nbytes = recipe.decode_slot_bytes(req.active_params)
        op = plane.kv_ckpt_op(req.recipe_key, w.worker_id, dst.worker_id,
                              nbytes, src_zone=w.zone, dst_zone=dst.zone)
        if not plane.ckpt_admits(op, t):
            sched.kv_ckpts_deferred += 1   # window full: next boundary
            return
        plane.commit_kv_ckpt(rid, op, now=t)
        sched.kv_ckpts += 1
        base = (self.cluster.peer_bw_cross if op.cross_zone
                else self.cluster.peer_bw_local)
        bw = base / (self._peer_streams.get(w.worker_id, 0) + 1)
        delay = op.nbytes / bw if op.nbytes > 0 else 0.0
        steps_at = req.steps_done
        t_land = t + delay

        def landed(op=op):
            if plane._inflight_ckpts.get(rid) is not op:
                return              # aborted (endpoint died): stale event
            src_w = sched.workers.get(op.src_worker)
            if src_w is None or src_w.frozen_s is not None:
                # the source died mid-transfer: the bytes never all left
                plane.kv_ckpt_aborted(rid, self.loop.now)
                return
            plane.kv_ckpt_completed(rid)
            req.ckpt_worker = op.worker_id
            req.ckpt_steps = steps_at
            req.ckpt_nbytes = op.nbytes

        if t_land <= self.loop.now:
            # lazily settled history: this transfer already finished in
            # simulated time (boundaries are materialised out of a bulk
            # settle).  Completing it synchronously keeps chronology
            # exact — the NEXT boundary in the same settle sees no
            # in-flight snapshot and supersedes this one, so the last
            # landed checkpoint is the newest whose transfer beat NOW
            # (for a crashed worker: beat the crash instant).
            landed()
        else:
            if delay > 0:
                self._take_peer_stream(w.worker_id, delay)
            self.loop.at(t_land, landed)

    # -- shared-filesystem contention (Challenge #5) -----------------------
    def _fs_bw(self) -> float:
        c = self.cluster
        return min(c.shared_fs_stream_bw,
                   c.shared_fs_bw / max(1, self._fs_streams + 1))

    def _with_fs_stream(self, duration: float) -> None:
        self._fs_streams += 1
        self.loop.after(duration, self._end_fs_stream)

    def _end_fs_stream(self) -> None:
        self._fs_streams = max(0, self._fs_streams - 1)

    def _take_peer_stream(self, src: str, duration: float) -> None:
        """Occupy one outbound stream on ``src``'s NIC for ``duration``."""
        self._peer_streams[src] = self._peer_streams.get(src, 0) + 1
        self.loop.after(duration, lambda: self._peer_streams.__setitem__(
            src, max(0, self._peer_streams.get(src, 1) - 1)))

    # -- staging time model -------------------------------------------------
    def _staging_cost(self, a: Assignment) -> float:
        """Seconds of context staging for a cold dispatch (0 when warm)."""
        req, w = a.request, a.worker
        recipe = self.sched.registry.recipes[req.recipe_key]
        mode = req.mode
        lib = w.library_for(recipe)
        if mode is NAIVE:
            # sandbox-per-task: deps via shared fs, weights re-downloaded
            # from the model hub, nothing reused (pv1).
            deps = recipe.element("deps")
            weights = recipe.element("weights")
            fs_bw = self._fs_bw()
            fetch = deps.nbytes_disk / fs_bw
            self._with_fs_stream(fetch)
            fetch += weights.nbytes_disk / self.cluster.internet_bw
            load = weights.nbytes(Tier.HOST) / w.device.disk_bw
            h2d = weights.nbytes(Tier.DEVICE) / w.device.h2d_bw
            return fetch + load + h2d + recipe.activation_s
        # partial / pervasive: the library stages against the local cache
        if a.peer_source is not None:
            base = (self.cluster.peer_bw_cross if a.cross_zone
                    else self.cluster.peer_bw_local)
            # source NIC is shared by its concurrent outbound transfers
            n = self._peer_streams.get(a.peer_source, 0)
            fetch_bw = base / (n + 1)
        else:
            fetch_bw = self._fs_bw()
        cost = lib.materialize_cost(w.device, fetch_bw=fetch_bw)
        a.moved_bytes = cost.fetch_bytes    # plan/executed byte accounting
        if cost.fetch_s > 0:
            if a.peer_source is not None:
                self._take_peer_stream(a.peer_source, cost.fetch_s)
            else:
                self._with_fs_stream(cost.fetch_s)
        return cost.total_s

    def _post_exec(self, a: Assignment) -> None:
        """Mode-dependent teardown after a task finishes (paper §5.2 obs 3)."""
        req, w = a.request, a.worker
        recipe = self.sched.registry.recipes[req.recipe_key]
        if req.mode is PERVASIVE:
            return                      # library stays resident
        lib = w.libraries.get(recipe.key)
        if lib is not None:
            lib.teardown()
        if req.mode is PARTIAL:
            # sandbox destroyed but registered disk artefacts survive;
            # elements still pinned by a co-resident library stay put
            for e in recipe.elements:
                if w.cache.tier_of(e.key) is not None \
                        and w.cache.pins(e.key) == 0:
                    w.cache.demote(e.key, Tier.DISK)
        else:                           # naive: nothing survives
            for e in recipe.elements:
                if w.cache.pins(e.key) == 0:
                    w.cache.drop(e.key)

    # -- dispatch loop --------------------------------------------------------
    def pump(self) -> None:
        while True:
            a = self.sched.route()
            if a is None:
                break
            self._start(a)
        # leftover idle workers: replicate hot recipes ahead of demand
        self._apply_warm_pool()
        # elastic supply reacts to the demand this round revealed
        # (re-entrancy is the hook owner's problem: Factory.step guards)
        if self.supply_hook is not None:
            self.supply_hook()
        # with a gateway installed, queued deadlines must fire as DES
        # events — an idle loop would otherwise never notice an expiry
        self._arm_deadline_timer()

    def _arm_deadline_timer(self) -> None:
        gw = self.sched.gateway
        if gw is None:
            return
        nd = gw.next_deadline()
        if nd is None:
            if self._deadline_timer is not None:
                self._deadline_timer.cancel()
                self._deadline_timer = None
            return
        t = max(nd + _EPS, self.loop.now)
        if self._deadline_timer is not None:
            if self._deadline_timer.t <= t + _EPS:
                return                  # an earlier/equal expiry is armed
            self._deadline_timer.cancel()

        def fire():
            self._deadline_timer = None
            self.pump()                 # route() expires overdue requests

        self._deadline_timer = self.loop.at(t, fire)

    def _meter_preemption(self, a: Assignment) -> None:
        """Price the KV bytes a preemption dispatch moves: the victim's
        decode cache spilling host-side, and — on the victim's return —
        the snapshot moving back (sim: the recipe's per-slot estimate)."""
        if a.preempt is None and not a.resumed:
            return
        plane = self.sched.plane
        key = a.request.recipe_key
        recipe = self.sched.registry.recipes[key]
        if a.preempt is not None:
            plane.record_kv_spill(
                key, a.worker.zone,
                recipe.decode_slot_bytes(a.preempt.active_params))
        if a.resumed:
            plane.record_kv_resume(
                key, a.worker.zone,
                recipe.decode_slot_bytes(a.request.active_params))

    def _ship_delay(self, a: Assignment, t0: float) -> float:
        """Price the KV handoff attached to a decode dispatch: occupy an
        outbound stream on the prefill worker's NIC, schedule the plane's
        landed event, and return the transfer seconds the admission must
        wait for.  The landed event is stale-safe — an eviction that
        already aborted the ship makes it a no-op."""
        op = a.kv_ship
        if op is None:
            return 0.0
        base = (self.cluster.peer_bw_cross if op.cross_zone
                else self.cluster.peer_bw_local)
        bw = base / (self._peer_streams.get(op.src_worker, 0) + 1)
        ship_s = op.nbytes / bw if op.nbytes > 0 else 0.0
        if ship_s > 0:
            self._take_peer_stream(op.src_worker, ship_s)
        a.request.ship_s += ship_s
        rid = a.request.request_id
        self.loop.at(t0 + ship_s,
                     lambda: self.sched.plane.kv_ship_completed(rid))
        return ship_s

    def _start_prefill(self, a: Assignment, t0: float,
                       staging_s: float) -> None:
        """A PREFILL dispatch occupies the worker for the FLOP-bound
        prompt pass, then hands the request back to the scheduler as a
        DECODE-phase requeue carrying its KV snapshot, priced at the
        recipe's per-slot estimate (the same pricing preemption spills
        use, so ship and spill bytes stay comparable)."""
        req, w = a.request, a.worker
        wid, tid = w.worker_id, req.request_id
        recipe = self.sched.registry.recipes[req.recipe_key]
        prefill_s = w.device.prefill_time(req.active_params,
                                          req.prompt_units)

        def staged():
            if wid in self.sched.workers and tid in self.sched.running \
                    and w.frozen_s is None:
                self.sched.on_staged(a)

        def done():
            cur = self.sched.running.get(tid)
            if cur is None or cur[1] != wid:
                return              # evicted mid-prefill: already requeued
            if w.frozen_s is not None:
                return              # crashed: nothing completed; the
                                    # detector's eviction requeues
            self.sched.on_prefill_done(
                a, t0, self.loop.now,
                kv_nbytes=recipe.decode_slot_bytes(req.active_params))
            self.pump()

        if not a.warm:
            self.loop.at(t0 + staging_s, staged)
        self.loop.at(t0 + staging_s + prefill_s, done)

    def _start(self, a: Assignment) -> None:
        # the manager is serial: one dispatch per manager_dispatch_s
        t0 = max(self.loop.now, self._manager_free) \
            + self.cluster.manager_dispatch_s
        self._manager_free = t0
        a.t_dispatch = t0
        self.sched.on_start(a)
        self._meter_preemption(a)
        req, w = a.request, a.worker
        wid = w.worker_id
        if a.join:
            run = self._streams.get((wid, req.recipe_key))
            if run is None:
                if a.kv_ship is not None:
                    # no batch to land on: the committed handoff dies too
                    self.sched.plane.kv_ship_aborted(req.request_id,
                                                     self.loop.now)
                return
            # the admission lands once the serial manager finishes this
            # dispatch (t0) plus any KV handoff from the prefill worker
            ship_s = self._ship_delay(a, t0)
            self.loop.at(t0 + ship_s, lambda: run.admit(a))
            return
        staging_s = 0.0 if a.warm else self._staging_cost(a)
        if req.phase == PREFILL:
            self._start_prefill(a, t0, staging_s)
            return
        ship_s = self._ship_delay(a, t0)
        if not req.exclusive:
            # founding member of a stream batch: hand the clock to a runner
            run = _StreamRun(self, a)
            self._streams[(wid, req.recipe_key)] = run
            if not a.warm:
                def staged(run=run):
                    if wid in self.sched.workers and run.alive() \
                            and run.w.frozen_s is None:
                        self.sched.on_staged(a)
                self.loop.at(t0 + staging_s, staged)
            self.loop.at(t0 + staging_s + ship_s, run.begin)
            return
        # deprecated run-to-completion batch: one completion event.  A
        # DECODE-phase exclusive already banked its prompt units as
        # steps_done, so only the remaining (decode) units run here.
        step_s = w.device.step_time(req.active_params, 1)
        infer_s = (req.n_units - req.steps_done) * step_s
        tid = req.request_id

        def staged():
            if wid in self.sched.workers and tid in self.sched.running \
                    and w.frozen_s is None:
                self.sched.on_staged(a)

        def complete():
            cur = self.sched.running.get(tid)
            if cur is None or cur[1] != wid:
                return                  # evicted mid-run; already requeued
                                        # (and possibly re-dispatched)
            if w.frozen_s is not None:
                return                  # crashed mid-run: no completion
            self.sched.on_complete(a, t0, self.loop.now,
                                   t_first_step=t0 + staging_s + ship_s
                                   + step_s)
            self._post_exec(a)
            self.pump()

        if not a.warm:
            self.loop.at(t0 + staging_s, staged)
        self.loop.at(t0 + staging_s + ship_s + infer_s, complete)

    # -- run ------------------------------------------------------------------
    def run(self, *, until: Optional[float] = None) -> float:
        self.pump()
        self.loop.run(until=until,
                      stop=lambda: self.sched.done
                      and not self.pending_arrivals)
        return self.sched.makespan()


class LiveExecutor(_PlanOpExecution):
    """Synchronous wall-clock executor: contexts and requests really run.

    ``fns[recipe_key]`` is the bound function ``fn(payloads, payload)``
    executed inside the library's address space for a deprecated
    run-to-completion task (paper Fig 3's ``infer_model``).

    ``step_fns[recipe_key]`` is the STREAM path: called once per decode
    step with the library payloads and the list of active member
    requests, it returns ``{request_id: step_output}``; outputs
    accumulate in ``results[request_id]`` (a list, one entry per step).
    Membership changes hands between calls: the step function binds
    joiners into a persistent slot pool (admission prefill), steps the
    whole pool through one cached ``decode_step``, and frees finished
    slots (:class:`repro.inference.streaming.StreamingDecoder` does
    exactly this for the PfF application); the executor feeds the pool's
    measured per-slot bytes back into the recipe's slot budget.

    All simulated workers share this container's device; what is real is
    the context lifecycle — import, weight materialisation, jit compile
    on first use, and reuse on subsequent invocations.

    Host spans (``repro.tracing``) name where the loop's time goes:
    ``repro.executor.dispatch`` per routing round (stat ``routed``) with
    ``repro.executor.route`` per ``Scheduler.route`` call,
    ``repro.executor.step`` per stream step (stats ``rows`` and ``step``,
    a running step number), ``repro.executor.complete`` around the
    library's step and its completions, and ``repro.executor.warm_pool``.
    """

    def __init__(self, scheduler: Scheduler,
                 fns: Optional[Dict[str, Callable[..., Any]]] = None,
                 *, warm_pool: Optional[WarmPoolPolicy] = None,
                 step_fns: Optional[Dict[str, Callable[..., Any]]] = None):
        self.sched = scheduler
        scheduler.clock = self.now
        self.fns = fns or {}
        self.step_fns = step_fns or {}
        self.warm_pool = warm_pool
        self.results: Dict[int, Any] = {}
        self._stream_assign: Dict[int, Assignment] = {}
        self._open: List[Tuple[Worker, str]] = []
        # (worker_id, key) -> decoder kv_resume_bytes_total last metered
        self._kv_resume_seen: Dict[Tuple[str, str], int] = {}
        self.staging_s = 0.0                # wall seconds materialising
        self._steps = 0                     # stream steps run (span ids)
        self._t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self._t0

    _now = now                          # deprecated alias

    def _apply_warm_pool(self) -> int:
        """Compile Replicate intents through the context plane and run the
        SAME plan ops the sim executes — here the loaders really run."""
        with span("repro.executor.warm_pool"):
            if self.warm_pool is None:
                return 0
            plane = self.sched.plane
            view = self.sched.view(now=self.now())
            intents = list(plane.recovery_intents(view))
            intents += self.warm_pool.intents(view)
            if not intents:
                return 0
            plan = plane.compile(intents, view)
            plane.commit(plan, now=view.now)
            self.execute_plan(plan)
            return len(plan.acquire_ops())

    def _make_ready(self, lib) -> None:
        """Materialise ``lib`` if it is not hosted yet (weights, compile),
        metering the wall time into ``staging_s``."""
        if not lib.ready:
            self.staging_s += lib.materialize().total_s

    # -- shared plan-op path: live staging really runs the loaders ---------
    def _materialize_op(self, op, w: Worker, recipe,
                        attempt: int = 0) -> None:
        lib = w.library_for(recipe)
        self._make_ready(lib)
        w.staging = False
        # live loaders do not move the plan's network bytes (everything is
        # on this container); account the op as priced
        self.sched.plane.op_completed(op)

    # -- dispatch -------------------------------------------------------
    def _run_exclusive(self, a: Assignment) -> None:
        req, w = a.request, a.worker
        recipe = self.sched.registry.recipes[req.recipe_key]
        lib = w.library_for(recipe)
        self._make_ready(lib)
        self.sched.on_staged(a)
        out = lib.invoke(self.fns[req.recipe_key], req.payload)
        self.results[req.request_id] = out
        self.sched.on_complete(a, a.t_dispatch, self.now())
        if req.mode is not PERVASIVE:
            lib.teardown()              # pay init again next task
        # warm-pool is demand-driven: it must run while work is still
        # queued, i.e. between tasks, not just per outer run() round
        self._apply_warm_pool()

    def _dispatch_all(self) -> bool:
        with span("repro.executor.dispatch") as round_span:
            routed = 0
            while True:
                with span("repro.executor.route"):
                    a = self.sched.route()
                if a is None:
                    break
                routed += 1
                self._dispatch(a)
            round_span.set_metadata(routed=routed)
        return routed > 0

    def _dispatch(self, a: Assignment) -> None:
        a.t_dispatch = self.now()
        req, w = a.request, a.worker
        self.sched.on_start(a)
        if req.phase == PREFILL:
            self._run_prefill(a)
            return
        if req.exclusive:
            self._run_exclusive(a)
            return
        self._stream_assign[req.request_id] = a
        if a.preempt is not None:
            self._suspend_victim(a)
        if not a.join:                  # founding member: open the batch
            lib = w.library_for(
                self.sched.registry.recipes[req.recipe_key])
            self._make_ready(lib)
            self.sched.on_staged(a)
            self._open.append((w, req.recipe_key))
        if a.kv_ship is not None:
            self._ship_kv(a)

    def _run_prefill(self, a: Assignment) -> None:
        """Run a PREFILL-phase dispatch to completion: materialise the
        recipe, emit the prompt-phase tokens through the step function's
        ``prefill`` entry, and leave the KV snapshot parked in this
        worker's decoder.  The request goes back to the scheduler as
        DECODE-phase work carrying the snapshot's MEASURED byte size —
        the plane prices any subsequent ship with real bytes.  A recipe
        whose step function cannot prefill without stepping falls back
        to colocated execution (phase cleared, request requeued)."""
        req, w = a.request, a.worker
        t_start = self.now()
        recipe = self.sched.registry.recipes[req.recipe_key]
        lib = w.library_for(recipe)
        self._make_ready(lib)
        self.sched.on_staged(a)
        prefill = getattr(self.step_fns.get(req.recipe_key), "prefill",
                          None)
        if prefill is None:
            self.sched.abort_prefill(a)
            return
        nbytes, toks = prefill(lib.context.payloads, req)
        self.results.setdefault(req.request_id, []).extend(toks)
        self.sched.on_prefill_done(a, t_start, self.now(),
                                   kv_nbytes=nbytes)

    def _ship_kv(self, a: Assignment) -> None:
        """Execute the KV handoff attached to a decode dispatch: pop the
        snapshot from the prefill worker's decoder and park it in the
        destination library's inbox; the step function adopts it before
        the request's first decode step, so decode resumes bit-exactly
        WITHOUT re-prefill.  A snapshot that died with its library
        (spill / eviction) aborts the ship — the decode admission falls
        back to a fresh prefill and nothing is metered as moved."""
        req, w = a.request, a.worker
        key = req.recipe_key
        plane = self.sched.plane
        src_w = self.sched.workers.get(a.kv_ship.src_worker)
        src_lib = src_w.libraries.get(key) if src_w is not None else None
        src_dec = (src_lib.context.payloads.get("_stream_decoder")
                   if src_lib is not None and src_lib.context is not None
                   else None)
        snap = (src_dec.export_suspended(req.request_id)
                if src_dec is not None else None)
        if snap is None:
            plane.kv_ship_aborted(req.request_id, self.now())
            return
        t0 = self.now()
        lib = w.library_for(self.sched.registry.recipes[key])
        if lib.context is None:
            lib.materialize()
        lib.context.payloads.setdefault("_kv_inbox", {})[
            req.request_id] = snap
        req.ship_s += self.now() - t0
        plane.kv_ship_completed(req.request_id,
                                moved_bytes=_kv_nbytes(snap.get("kv")))

    def _suspend_victim(self, a: Assignment) -> None:
        """Spill the preempted member's KV host-side through the stream
        decoder BEFORE the next step runs, so the interactive admission
        finds the slot free and the victim can later resume without
        re-prefill.  Without a decoder (step_fn never ran) there is no
        device state to save — the victim simply restarts."""
        victim, w, key = a.preempt, a.worker, a.request.recipe_key
        lib = w.libraries.get(key)
        dec = (lib.context.payloads.get("_stream_decoder")
               if lib is not None and lib.context is not None else None)
        nbytes = dec.suspend(victim.request_id) if dec is not None else 0
        if nbytes:
            victim.kv_nbytes = nbytes   # measured, not the sim estimate
            self.sched.plane.record_kv_spill(key, w.zone, nbytes)
        else:                           # nothing saved: back to scratch
            victim.suspended = False
            victim.suspended_on = None
            victim.steps_done = 0
            victim.t_first_step = None

    # -- the live step loop ---------------------------------------------
    def _step_streams(self) -> bool:
        stepped = False
        for w, key in list(self._open):
            if self.sched.workers.get(w.worker_id) is not w:
                self._open.remove((w, key))     # worker evicted mid-batch
                continue
            lib = w.libraries.get(key)
            if lib is None or not lib.batch:
                self._open.remove((w, key))
                self.sched.close_stream(w.worker_id, key)
                continue
            lib.activate()
            members = list(lib.batch.values())
            step_fn = self.step_fns.get(key)
            self._steps += 1
            if step_fn is not None:
                with span("repro.executor.step", rows=len(members),
                          step=self._steps):
                    self._run_step_fn(step_fn, w, key, lib, members)
            with span("repro.executor.complete"):
                finished = lib.step()
                now = self.now()
                for r in members:
                    if r.t_first_step is None:
                        r.t_first_step = now
                for r in finished:
                    a = self._stream_assign.pop(r.request_id, None)
                    if a is not None:
                        self.sched.on_complete(a, a.t_dispatch, now,
                                               t_first_step=r.t_first_step)
            stepped = True
            if not lib.batch:
                self._open.remove((w, key))
                self.sched.close_stream(w.worker_id, key)
        return stepped

    def _run_step_fn(self, step_fn, w: Worker, key: str, lib,
                     members: list) -> None:
        outs = step_fn(lib.context.payloads, members)
        for rid, frag in outs.items():
            self.results.setdefault(rid, []).append(frag)
        # slot budgets from measured memory: a step function that hosts a
        # slot-pool decoder exposes the REAL per-slot cache footprint once
        # its cache exists; feed it back so this recipe's slot budgets
        # stop using the KV_BYTES_PER_PARAM analytic guess.
        dec = lib.context.payloads.get("_stream_decoder")
        measured = int(getattr(dec, "measured_slot_bytes", 0) or 0)
        if measured and measured != lib.recipe.measured_slot_bytes:
            lib.recipe.record_slot_bytes(measured)
        # meter KV snapshots the decoder restored this step (resume
        # happens inside the step_fn, so delta-track it)
        total = int(getattr(dec, "kv_resume_bytes_total", 0) or 0)
        seen = self._kv_resume_seen.get((w.worker_id, key), 0)
        if total > seen:
            self.sched.plane.record_kv_resume(key, w.zone, total - seen)
            self._kv_resume_seen[(w.worker_id, key)] = total

    def run(self) -> float:
        while not self.sched.done:
            progressed = self._dispatch_all()
            progressed |= self._step_streams()
            if not progressed:
                gw = self.sched.gateway
                nd = gw.next_deadline() if gw is not None else None
                if nd is not None:
                    # queued work is deadline-gated, not unplaceable:
                    # wait for the expiry (or preemption slack) to open
                    time.sleep(min(max(nd - self.now(), 0.0), 0.05)
                               + 0.001)
                    continue
                raise RuntimeError(
                    "deadlock: requests queued but no worker can host "
                    "them (check worker shapes vs recipe footprints)")
            self._apply_warm_pool()
        return self.sched.makespan()

"""The system under test, wired as ``launch/serve.py``'s ``serve()`` wires it.

Application → Gateway → Scheduler → LiveExecutor → ``make_pff_step_fn``
→ StreamingDecoder (paged KV, prefix index) → ``prefill_into_pages`` /
``decode_step`` → the paged Pallas decode kernel, with ONE worker on the
chip.  Only public entries of the program are used.

The benchmark sees the program through one seam: it wraps the step
function the executor calls once per step.  The wrapper stamps the host
time at which each step's tokens reached the host, feeds the arrival loop
(``LiveExecutor.run`` returns when the scheduler is idle, so arrivals have
to come from here), records what each step computed, and writes the
benchmark's own host spans into the profiler trace: ``bench.step_fn``
around the program's step, ``bench.record`` around the harness's own
book-keeping after it.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax

from .traffic import RequestSpec, Traffic

PAGE = 64                       # the decoder's default page size

# host span names the trace reduction attributes idle gaps to
SPAN_STEP = "bench.step_fn"
SPAN_RECORD = "bench.record"
SPAN_SUBMIT = "bench.submit"
SPAN_WAIT = "bench.wait_arrival"


@dataclass
class StepRecord:
    """What one call of the step function did, as the harness saw it."""
    t_call: float
    t_return: float
    rows: int                            # members in the call
    prefill_rows: List[Tuple[int, int]]  # (tail tokens, shared base) per row
    decode_ctx: List[int]                # n_valid of each decoded row
    shared_delta: int = 0                # program counters, this step
    prefill_delta: int = 0

    @property
    def labels_agree(self) -> bool:
        """Whether the shared bases the harness gave this step's admissions
        sum to the prompt tokens the decoder says it mapped from resident
        pages."""
        return sum(b for _n, b in self.prefill_rows) == self.shared_delta


@dataclass
class Outcome:
    """One submitted request, on the harness's clock."""
    spec: RequestSpec
    rid: int
    due: float
    tokens_at: List[float] = field(default_factory=list)
    shared_base: int = 0


class ServedPool:
    """One worker serving the PfF recipe of ``model_cfg`` on this device."""

    def __init__(self, model_cfg, mix: dict, traffic: Traffic,
                 weight_seed: int, device_name: Optional[str] = None):
        from repro.cluster import (Application, ClassPolicy, Gateway,
                                   LiveExecutor, Scheduler, Worker,
                                   local_device_model)
        from repro.core import MODES
        from repro.inference import build_context_recipe, make_pff_step_fn

        self.cfg = model_cfg
        self.mix = mix
        self.traffic = traffic
        recipe = build_context_recipe(model_cfg, mix["template"],
                                      max_len=mix["max_len"],
                                      seed=weight_seed)
        self.sched = Scheduler()
        self.app = Application(self.sched, default_mode=MODES["pervasive"])
        self.key = self.app.register(recipe)
        self.sched.add_worker(Worker(local_device_model(device_name),
                                     zone="z0"))
        # serve()'s gateway: interactive requests queue at most 64 deep and
        # time out after 60 s in the queue; batch requests queue unbounded
        self.gateway = Gateway(self.sched, interactive=ClassPolicy(
            max_queue=64, overflow="reject", deadline_s=60.0))
        self._inner = make_pff_step_fn(mix["prompt_len"],
                                       max_len=mix["max_len"])
        self.ex = LiveExecutor(self.sched, step_fns={self.key: self._step})
        self.clock: Callable[[], float] = self.ex.now
        self.payloads: Optional[dict] = None
        self.outcomes: Dict[int, Outcome] = {}
        self.steps: List[StepRecord] = []
        self.after_step: Callable[[int], None] = lambda n_finishing: None
        self._live: Dict[int, Tuple[int, ...]] = {}
        self._next_index = 0

    # -- requests -------------------------------------------------------
    def submit(self, due: float) -> Outcome:
        """Submit the traffic's next request, due at ``due``."""
        spec = self.traffic.request(self._next_index)
        self._next_index += 1
        with jax.profiler.TraceAnnotation(SPAN_SUBMIT):
            req = self.app.submit(self.key, decode_steps=spec.decode_tokens,
                                  payload=spec.claim, arrival_s=due,
                                  slo=spec.slo)
        out = Outcome(spec, req.request_id, due)
        self.outcomes[req.request_id] = out
        return out

    @property
    def decoder(self):
        return (self.payloads or {}).get("_stream_decoder")

    def counters(self) -> Tuple[int, int]:
        """(shared, prefilled) prompt tokens, the decoder's own counters."""
        dec = self.decoder
        if dec is None:
            return 0, 0
        return dec.shared_tokens_total, dec.prefill_tokens_total

    # -- the seam ---------------------------------------------------------
    def _shared_base(self, toks: Tuple[int, ...]) -> int:
        """Whole-page prompt prefix a fresh row finds resident: the longest
        page-aligned prefix it shares with a row the decoder holds, capped
        so the tail keeps at least one token (the decoder's rule)."""
        best = 0
        cap = (len(toks) - 1) // PAGE
        for other in self._live.values():
            n = min(cap, len(other) // PAGE)
            j = 0
            while j < n and toks[j * PAGE:(j + 1) * PAGE] == \
                    other[j * PAGE:(j + 1) * PAGE]:
                j += 1
            best = max(best, j * PAGE)
        return best

    def _step(self, payloads, members):
        self.payloads = payloads
        t_call = self.clock()
        before = self.counters()
        with jax.profiler.TraceAnnotation(SPAN_STEP):
            outs = self._inner(payloads, members)
        t = self.clock()
        after = self.counters()
        with jax.profiler.TraceAnnotation(SPAN_RECORD):
            prefill_rows, decode_ctx = [], []
            for r in members:
                out = self.outcomes[r.request_id]
                if r.request_id not in self._live:
                    base = self._shared_base(out.spec.tokens)
                    out.shared_base = base
                    self._live[r.request_id] = out.spec.tokens
                    prefill_rows.append((len(out.spec.tokens) - base, base))
                else:
                    decode_ctx.append(len(out.spec.tokens)
                                      + len(out.tokens_at))
            for rid in outs:
                self.outcomes[rid].tokens_at.append(t)
            self.steps.append(StepRecord(
                t_call, t, len(members), prefill_rows, decode_ctx,
                after[0] - before[0], after[1] - before[1]))
            finishing = 0
            for r in members:
                if r.steps_done + 1 >= r.n_units:
                    finishing += 1
                    self._live.pop(r.request_id, None)
        self.after_step(finishing)
        return outs

    def run(self) -> None:
        """Serve until the scheduler is idle."""
        self.ex.run()

    # -- warm-up ----------------------------------------------------------
    def warm_prefill_shapes(self, rows: List[int], buckets: List[int]) -> int:
        """Compile every admission-prefill shape (rows bucket x token
        bucket) the traffic can produce, through the decoder's public
        admission path, on prompts of the traffic itself.  Every row is a
        different request, so no row finds another's pages.  Runs with the
        pool empty; returns how many admissions it made."""
        dec = self.decoder
        if not rows:
            return 0
        prompts = [self.traffic.request(i).tokens
                   for i in range(self._next_index,
                                  self._next_index + max(rows) *
                                  self.mix["docs_shared_by"] + 1,
                                  self.mix["docs_shared_by"])]
        n = 0
        rid = -1
        for length in buckets:
            for b in rows:
                if b > dec.pool.free:
                    continue
                rids = []
                for p in prompts[:b]:
                    toks = list(p[:length]) + [p[-1]] * (length - len(p))
                    dec.ensure_tokens(rid, toks)
                    rids.append(rid)
                    rid -= 1
                dec.step(rids)
                for r in rids:
                    dec.finish(r)
                n += 1
        return n


def wait_until(clock: Callable[[], float], t: float) -> None:
    """Sleep until ``clock() >= t``."""
    with jax.profiler.TraceAnnotation(SPAN_WAIT):
        while True:
            dt = t - clock()
            if dt <= 0:
                return
            time.sleep(min(dt, 0.002))

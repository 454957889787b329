"""Prefill/decode disaggregation: throughput per pooled FLOP + KV_SHIP.

The disaggregation tentpole claim: on a HETEROGENEOUS pool the two
inference phases rank devices differently — prefill is FLOP-bound
(~150x spread across the catalog), decode is HBM-bound (~10x spread) —
so phase-aware routing (prefill to compute-rich workers, decode to
memory-side slot pools, KV handoff over the context plane's KV_SHIP op
class) completes the same work in less wall-clock than colocated
routing on the SAME pool, i.e. strictly more throughput per pooled
TFLOP (arXiv 2504.15303).

Two DES runs on an identical mixed pool (2x RTX 6000 Ada + 6x A10, two
zones, so ships cross both peer link classes):

* ``colocated``      — phase-blind routing: each request prefills and
  decodes wherever the request lands.
* ``disaggregated``  — ``Scheduler(disaggregate=True)``: requests
  phase-split at submit; decode placement scores every candidate by
  estimated decode seconds PLUS the KV handoff over the peer link, so
  the same-worker fast path wins whenever shipping would lose.

Reported: makespan, completed units, units/s/pooled-TFLOP, ships vs
local fast-path decodes, shipped KV bytes by landing zone, and the
per-phase latency breakdown (prefill / ship / decode percentiles).

The LIVE section drives real :class:`StreamingDecoder` instances on a
two-worker rig built to force ships (compute-rich/slow-HBM prefill
device, fast-HBM decode device): after prefill the KV snapshot is
exported bit-exact (`export_suspended`), parked in the destination
worker's inbox, adopted into its slot pool, and decode resumes WITHOUT
re-prefill — the full token stream must be BIT-EXACT vs a colocated run
of the same claims.

``--smoke`` (the CI guard): FAILS if disaggregated throughput falls
below colocated at equal completed work, if no KV handoff actually
happened, if shipped tokens diverge from colocated, or
if any plan/moved/inflight KV byte accounting leaks.
"""
from __future__ import annotations

import argparse
import sys

from repro.cluster import (Application, GPU_CATALOG, LiveExecutor,
                           Scheduler, Worker, latency_summary, format_latency,
                           format_zone_bytes, make_sim, pool_rate)
from repro.cluster.hardware import DeviceModel

from .common import ACTIVE_PARAMS, RECIPE, Report

# -- sim scenario -----------------------------------------------------------
# two zones of 4: z0 = both Adas + 2 A10s, z1 = 4 A10s — ships exercise
# the local AND cross peer link classes
POOL = [GPU_CATALOG["NVIDIA RTX 6000 Ada Generation"]] * 2 \
    + [GPU_CATALOG["NVIDIA A10"]] * 6
WORKERS_PER_ZONE = 4
N_REQS = 120
PROMPT_UNITS = 4
DECODE_STEPS = 32
ARRIVAL_EVERY = 0.25
UNTIL = 10_000.0


def _run_sim(name: str, *, disaggregate: bool):
    sched, ex, fac = make_sim(devices=POOL,
                              workers_per_zone=WORKERS_PER_ZONE,
                              disaggregate=disaggregate)
    app = Application(sched)
    key = app.register(RECIPE, active_params=ACTIVE_PARAMS)
    specs = [dict(recipe_key=key, prompt_units=PROMPT_UNITS,
                  decode_steps=DECODE_STEPS, arrival_s=i * ARRIVAL_EVERY)
             for i in range(N_REQS)]
    app.submit_stream(ex, specs)
    fac.reconcile(len(POOL))
    ex.run(until=UNTIL)
    assert sched.done, f"{name}: run hit the {UNTIL:.0f}s safety net"
    return sched


def _units_done(sched) -> int:
    return sum(r.n_units for r in sched.records if r.outcome == "done")


def _assert_no_kv_leaks(sched):
    """Drained run: every planned byte moved, every ship either landed
    or was refunded, nothing in flight."""
    assert sched.plane.planned.as_dict() == sched.plane.moved.as_dict(), \
        "planned != moved: a KV_SHIP (or staging op) leaked bytes"
    assert sched.plane.inflight_ops == 0, \
        f"{sched.plane.inflight_ops} op(s) still in flight after drain"
    assert not sched.running, f"requests stuck running: {sched.running}"
    assert all(not lane for lane in sched.lanes.values()), "non-empty lane"
    kv = sched.plane.kv_summary()
    by_zone = sum(getattr(sched.plane, "kv_shipped", {}).values())
    assert by_zone == kv["shipped_bytes"], \
        f"per-zone kv_shipped {by_zone} != shipped_bytes " \
        f"{kv['shipped_bytes']}"


def sim_section(smoke: bool):
    runs = {name: _run_sim(name, disaggregate=d)
            for name, d in (("colocated", False), ("disaggregated", True))}
    pooled_tflops = sum(d.tflops for d in POOL)
    rep = Report(
        f"prefill/decode disaggregation: {N_REQS} requests "
        f"({PROMPT_UNITS}u prefill + {DECODE_STEPS}u decode) on "
        f"2x RTX 6000 Ada + 6x A10 ({pooled_tflops:.0f} pooled TFLOPs)",
        ["run", "makespan s", "units", "units/s/TFLOP", "ships",
         "local fast-path", "shipped GB"])
    tput = {}
    for name, sched in runs.items():
        units = _units_done(sched)
        mk = sched.makespan()
        tput[name] = units / mk / pooled_tflops
        kv = sched.plane.kv_summary()
        rep.add(name, f"{mk:.1f}", units, f"{tput[name]:.4f}",
                sched.kv_ships, sched.local_decodes,
                f"{kv['shipped_bytes'] / 1e9:.2f}")
    rep.print()

    dis, col = runs["disaggregated"], runs["colocated"]
    gain = tput["disaggregated"] / tput["colocated"]
    # the decode-capacity view the router balances against: every device
    # counts toward decode (prefill workers backfill decode slots)
    print(f"pool rate: prefill {pool_rate(POOL, ACTIVE_PARAMS, phase='prefill'):.1f} u/s, "
          f"decode {pool_rate(POOL, ACTIVE_PARAMS, phase='decode'):.1f} u/s")
    print(f"throughput/pooled-TFLOP: {gain:.2f}x colocated "
          f"({dis.kv_ships} ship(s), {dis.local_decodes} same-worker "
          f"fast path(s), {dis.prefills_done} prefill(s))")
    print(format_zone_bytes(dis.plane, label="disaggregated"))
    print(format_latency(latency_summary(dis.records),
                         label="disaggregated"))
    for sched in runs.values():
        _assert_no_kv_leaks(sched)
    if smoke:
        assert _units_done(dis) == _units_done(col) > 0, \
            "runs completed unequal work — the comparison is vacuous"
        assert dis.kv_ships > 0, \
            "no KV handoff happened — KV_SHIP is dead code here"
        assert dis.local_decodes > 0, \
            "no same-worker fast path taken — the ship-vs-local rule " \
            "never chose local"
        assert dis.prefills_done == N_REQS, \
            f"{dis.prefills_done} prefills for {N_REQS} requests"
        assert gain >= 1.0, \
            f"disaggregated throughput is {gain:.2f}x colocated (< 1x): " \
            "phase-aware routing lost on its home turf"
        summ = latency_summary(dis.records)
        assert summ.get("n_phased", 0) == N_REQS, "phase latency missing"
        assert summ.get("n_shipped", 0) == dis.kv_ships
        print("smoke OK: disaggregation >= colocated throughput at equal "
              "work, ships metered, zero KV byte leaks")


# -- live shipped-KV token exactness ----------------------------------------
# a rig built to make shipping WIN: the prefill device is compute-rich
# but decodes slowly (weak HBM); the decode device is the reverse — so
# after each prefill the router's score favours paying the handoff
PREFILL_RIG = DeviceModel("prefill-rig", 2024, 1, 1.0, 24, 500e6, 8e9,
                          tflops=500.0)
DECODE_RIG = DeviceModel("decode-rig", 2024, 1, 0.08, 80, 500e6, 8e9,
                         tflops=5.0)
LIVE_CLAIMS = 6
LIVE_PROMPT_UNITS = 3
LIVE_DECODE_STEPS = 8


def _run_live(claims, recipe, *, disaggregate: bool):
    from repro.inference import make_pff_step_fn

    sched = Scheduler(disaggregate=disaggregate)
    app = Application(sched)
    key = app.register(recipe)
    sched.add_worker(Worker(PREFILL_RIG))
    sched.add_worker(Worker(DECODE_RIG))
    for c in claims:
        app.submit(key, prompt_units=LIVE_PROMPT_UNITS,
                   decode_steps=LIVE_DECODE_STEPS, payload=c)
    ex = LiveExecutor(sched, step_fns={key: make_pff_step_fn()})
    ex.run()
    # submission order, not request_id: ids are process-global
    toks = [ex.results[r.request_id] for r in app.requests]
    return toks, sched


def live_section(smoke: bool):
    from repro.configs import get_smoke_config
    from repro.data import generate_claims
    from repro.inference import build_context_recipe

    print("\n== live shipped-KV decode: token exactness + accounting ==")
    cfg = get_smoke_config("smollm2-1.7b")
    claims = generate_claims(LIVE_CLAIMS, seed=2)
    recipe = build_context_recipe(cfg, "with_evidence")
    base, _ = _run_live(claims, recipe, disaggregate=False)
    dis, sched = _run_live(claims, recipe, disaggregate=True)
    kv = sched.plane.kv_summary()
    assert base == dis, "shipped-KV decode diverged from colocated"
    assert sched.kv_ships > 0, "the rig never shipped — scoring regression"
    assert sched.prefills_done == LIVE_CLAIMS
    _assert_no_kv_leaks(sched)
    print(f"{LIVE_CLAIMS} requests bit-exact vs colocated "
          f"({sched.kv_ships} ship(s), {kv['shipped_bytes']} KV bytes "
          f"handed off, {sched.local_decodes} local)")
    if smoke:
        print("smoke OK: shipped-KV decode token-exact")


def main(smoke: bool = False) -> int:
    sim_section(smoke)
    live_section(smoke)
    return 0


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: fail if disaggregation loses to "
                         "colocated, ships never happen, shipped tokens "
                         "diverge, or KV byte accounting leaks")
    args = ap.parse_args()
    sys.exit(main(smoke=args.smoke))

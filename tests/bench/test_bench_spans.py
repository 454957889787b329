"""The program's host spans, reduced (``harness/spans.py``): the device's
idle time split by the innermost span it fell in, and the decoder's launch
counters; on hand-made spans, on a traced CPU run of the harness, and on
recorded TPU traces.  Also pins what the benchmark's existing readers and
``idle_gaps`` read on the first recorded trace, which holds no program
spans."""
import pathlib
import time
from types import SimpleNamespace

import pytest

import benchpath  # noqa: F401
from harness import cell, peaks, report, spans as sp, trace as tr

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000                      # nanoseconds


def hand_trace():
    """Window [0, 100] ms; the chip busy in [10, 20], [30, 40], [60, 70]."""
    E = tr.Event
    ops = [E("fusion.1", 10 * MS, 20 * MS), E("fusion.2", 30 * MS, 40 * MS),
           E("fusion.3", 60 * MS, 70 * MS)]
    return tr.Trace([("bench.window_start", 0, 1),
                     ("bench.window_end", 100 * MS, 100 * MS + 1)],
                    {"/device:TPU:0": {tr.OPS_LINE: ops}})


def S(name, start_ms, end_ms, line="python", **stats):
    return sp.Span(name, start_ms * MS, end_ms * MS, line, stats)


def hand_spans():
    """One step on the driving thread, launches, and spans of another
    thread that the idle split must not read."""
    return [
        S("bench.window_start", 0, 0),
        S("repro.executor.dispatch", 0, 8, routed=32),
        S("repro.executor.route", 1, 3),
        S("repro.executor.step", 8, 50, rows=32, step=1),
        S("bench.step_fn", 9, 45),
        S("repro.decoder.step", 9.5, 44, rows=32),
        S("repro.decoder.membership", 9.5, 10),
        S("repro.decoder.launch", 12, 14, program="decode_step",
          pages_in_use=60, pages_reserved=224),
        S("repro.decoder.fetch", 14, 41),
        S("repro.decoder.sample", 41, 43),
        S("bench.record", 45, 47),
        S("repro.executor.complete", 50, 55),
        # another thread: launches count, its step does not cover idle
        S("repro.executor.step", 55, 100, line="main"),
        S("repro.decoder.launch", 20, 21, line="main",
          program="prefill_into_pages", tokens=2400, padded_tokens=10560),
        S("repro.decoder.launch", 22, 23, line="main",
          program="prefill_into_slots", tokens=100, padded_tokens=128),
        S("repro.decoder.launch", 80, 81, line="main",
          program="decode_step", pages_in_use=100, pages_reserved=224),
        S("repro.decoder.launch", 150, 151, line="main",   # after the window
          program="decode_step", pages_in_use=224, pages_reserved=224),
    ]


def test_segments_are_named_by_the_innermost_open_span():
    segs = sp.innermost([S("a", 0, 10), S("b", 2, 4), S("c", 4, 12),
                         S("d", 20, 30)])
    # c ends after its parent a: it is clipped to a's end
    assert [(s / MS, e / MS, n) for s, e, n in segs] == [
        (0, 2, "a"), (2, 4, "b"), (4, 10, "c"), (20, 30, "d")]


def test_idle_is_split_by_the_driving_threads_innermost_span():
    idle = sp.idle_by_span(hand_trace(), hand_spans())
    ms = {n: round(s * 1e3, 6) for n, s in idle.items()}
    # gaps [0,10], [20,30], [40,60], [70,100]; [55,60] and [70,100] lie
    # outside every driving-thread span and are left out
    assert ms == {"repro.executor.dispatch": 6, "repro.executor.route": 2,
                  "repro.executor.step": 4, "bench.step_fn": 1.5,
                  "repro.decoder.membership": 0.5, "repro.decoder.fetch": 11,
                  "repro.decoder.sample": 2, "repro.decoder.step": 1,
                  "bench.record": 2, "repro.executor.complete": 5}


def test_idle_shares_of_the_executor_and_the_decoder():
    t, host = hand_trace(), hand_spans()
    executor = sp.idle_share(t, host, "repro.executor.")
    decoder = sp.idle_share(t, host, "repro.decoder.")
    assert executor == pytest.approx(17.0)
    assert decoder == pytest.approx(14.5)
    assert executor + decoder <= 100.0 * (1 - t.busy_s() / t.window_s)


def test_launch_counters_sum_over_the_window():
    t, host = hand_trace(), hand_spans()
    assert sp.kv_page_use_share(t, host) == pytest.approx(
        100.0 * 160 / 448)
    assert sp.prefill_useful_share(t, host) == pytest.approx(
        100.0 * 2500 / 10688)


def test_without_program_spans_nothing_is_read():
    t = hand_trace()
    bench_only = [s for s in hand_spans() if s.name.startswith("bench.")]
    for host in ([], bench_only):
        assert sp.idle_share(t, host, "repro.executor.") is None
        assert sp.kv_page_use_share(t, host) is None
        assert sp.prefill_useful_share(t, host) is None
    no_device = tr.Trace(t.spans, {})
    assert sp.idle_share(no_device, hand_spans(), "repro.decoder.") is None


# -- a traced CPU run of the harness ---------------------------------------

TRACED_MIX = {"warmup_requests": 8, "outstanding": 8, "prefill_rows": [8],
              "reference_requests": 6}
EXECUTOR = {"repro.executor.dispatch", "repro.executor.route",
            "repro.executor.step", "repro.executor.complete",
            "repro.executor.warm_pool"}
DECODER = {"repro.decoder.step", "repro.decoder.membership",
           "repro.decoder.pages", "repro.decoder.table_sync",
           "repro.decoder.launch", "repro.decoder.fetch",
           "repro.decoder.sample"}


def within(inner, outers):
    return any(o.line == inner.line and o.start <= inner.start
               and inner.end <= o.end for o in outers)


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """The shared-doc mix on the smoke preset, 3 s traced on the CPU,
    with every admission shape of its 8-row cohorts warmed up."""
    bench = cell.load_benchmark()
    trace_dir = tmp_path_factory.mktemp("trace")
    run = cell.run_cell("smollm2.pff-sweep.shared-doc", 4_000_000_011, 3.0,
                        trace_dir=str(trace_dir), process_start=time.time(),
                        smoke=True, device_name="TPU v5e",
                        mix_overrides=TRACED_MIX, bench=bench)
    path = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    return run, tr.Trace.from_file(str(path)), sp.read(str(path))


def test_a_traced_run_writes_every_span_nested_on_the_driving_thread(
        traced_run):
    run, t, host = traced_run
    # pool growth ran in the warm-up, before the trace
    # (tests/test_tracing.py sees its span)
    assert {s.name for s in host if s.name.startswith("repro.")} == \
        EXECUTOR | DECODER
    steps = sorted((s for s in host if s.name == "bench.step_fn"),
                   key=lambda s: s.start)
    executor_steps = [s for s in host if s.name == "repro.executor.step"]
    # the harness stops the trace from inside the window's last executor
    # step, so that one span is never written
    assert len(steps) > 10
    assert [s for s in steps if not within(s, executor_steps)] == steps[-1:]
    assert all(within(s, steps) for s in host if s.name in DECODER)
    assert sp.driving(host) == host             # one thread drives it all
    ids = [s.stats["step"] for s in executor_steps]
    assert ids == sorted(set(ids))
    assert {s.stats["rows"] for s in executor_steps} == {8}


def test_a_traced_runs_launch_counters_agree_with_the_decoder(traced_run):
    run, t, host = traced_run
    runs = sp.launches(t, host, sp.DECODE + sp.PREFILL)
    assert runs and all(r.stats["new_shape"] == 0 for r in runs)
    assert all(0 < r.stats["pages_in_use"] <= r.stats["pages_reserved"]
               for r in runs)
    w = run.window
    window_steps = [s for s in w.steps
                    if s.t_call >= w.trace_t0 and s.t_return <= w.trace_t1]
    prefills = sp.launches(t, host, sp.PREFILL)
    assert sum(r.stats["tokens"] for r in prefills) == \
        sum(s.prefill_delta for s in window_steps) > 0
    assert len(runs) == len(window_steps)
    assert 0 < sp.kv_page_use_share(t, host) <= 100
    assert 0 < sp.prefill_useful_share(t, host) <= 100


# -- recorded TPU traces ---------------------------------------------------

# 0.6 s of smollm2.pff-sweep.shared-doc on one TPU v5 lite, recorded
# before the program had spans: one 32-row admission and two decode steps
OLD = DATA / "smollm2-shared-doc-0.6s.xplane.pb"


def old_context():
    """The old recording with step records made to fit it (harness clock
    0 at the window's start): the admission's 32 rows, 30 of them mapping
    a 256-token shared prefix, then two 32-row decode steps."""
    t = tr.Trace.from_file(str(OLD))
    N = SimpleNamespace
    tails = [(300, 0), (310, 0)] + [(40 + i, 256) for i in range(30)]
    steps = [N(t_call=0.030, t_return=0.455, rows=32, prefill_rows=tails,
               decode_ctx=[], shared_delta=30 * 256,
               prefill_delta=sum(n for n, _b in tails)),
             N(t_call=0.455, t_return=0.572, rows=32, prefill_rows=[],
               decode_ctx=[300 + i for i in range(32)], shared_delta=0,
               prefill_delta=0),
             N(t_call=0.572, t_return=0.690, rows=32, prefill_rows=[],
               decode_ctx=[301 + i for i in range(32)], shared_delta=0,
               prefill_delta=0)]
    window = N(steps=steps, trace_t0=0.0, trace_t1=t.window_s)
    cfg = cell.load_config(cell.load_benchmark(), "smollm2-1.7b")
    return report.Context(N(window=window, cfg=cfg), t,
                          peaks.peaks("TPU v5 lite"))


# read by the benchmark's readers before the program had spans
OLD_READINGS = {"batch_rows_mean": 32.0,
                "prefix_hit_share": 77.38035264483628,
                "prefill_share": 64.7299170110424,
                "paged_decode_roofline": 1.9940110965548796,
                "step_mfu": 5.512043628115038,
                "device_idle_share.sweep": 7.780113963728608}


@pytest.mark.parametrize("name", sorted(OLD_READINGS))
def test_existing_readers_read_the_old_trace_as_before(name):
    assert report.reader(name)(old_context()) == pytest.approx(
        OLD_READINGS[name], rel=1e-12)


def test_idle_gaps_of_the_old_trace_are_named_as_before():
    gaps = tr.Trace.from_file(str(OLD)).idle_gaps()
    total = {}
    for name, secs in gaps:
        total[name] = total.get(name, 0.0) + secs
    assert len(gaps) == 77
    assert total == {"program": pytest.approx(0.037516133, abs=1e-9),
                     "bench.step_fn": pytest.approx(0.015929262, abs=1e-9)}
    assert sp.read(str(OLD)) and not any(
        s.name.startswith("repro.") for s in sp.read(str(OLD)))


# 0.69 s of smollm2.pff-sweep.shared-doc on one TPU v5 lite with the
# program's spans: a 32-row admission (its routing round first) and two
# decode steps
SPANS = DATA / "smollm2-shared-doc-spans-0.7s.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return tr.Trace.from_file(str(SPANS)), sp.read(str(SPANS))


def test_recorded_trace_names_its_programs_and_spans(recorded):
    t, host = recorded
    assert {e.name.split("(")[0] for e in t.events(tr.MODULES_LINE)} == \
        {"jit_decode_step", "jit_prefill_into_pages"}
    assert {s.name for s in host if s.name.startswith("repro.")} == \
        EXECUTOR | DECODER
    assert {s.line for s in sp.driving(host)} == {"python3"}
    runs = sp.launches(t, host, sp.DECODE + sp.PREFILL)
    assert [(r.stats["program"], r.stats["tokens"], r.stats["padded_tokens"],
             r.stats["pages_in_use"]) for r in runs] == [
        ("prefill_into_pages", 2468, 10496, 50), ("decode_step", 32, 32, 52),
        ("decode_step", 32, 32, 55)]
    assert {r.stats["pages_reserved"] for r in runs} == {224}


def test_recorded_trace_reduces_to_hand_checked_values(recorded):
    t, host = recorded
    # launches: pages 52 + 55 of 2 x 224; tokens 2468 of 32 x 328
    assert sp.kv_page_use_share(t, host) == pytest.approx(100 * 107 / 448)
    assert sp.prefill_useful_share(t, host) == pytest.approx(
        100 * 2468 / 10496)
    ms = {n: s * 1e3 for n, s in sp.idle_by_span(t, host).items()}
    executor = sum(v for n, v in ms.items() if n.startswith("repro.exec"))
    decoder = sum(v for n, v in ms.items() if n.startswith("repro.deco"))
    # route 10.164 + dispatch 3.109 + step 0.152 + complete 0.065
    # + warm_pool 0.001 ms
    assert executor == pytest.approx(13.492, abs=1e-3)
    # fetch 10.942 + sample 4.735 + membership 3.734 + pages 1.361
    # + table_sync 1.075 + step 0.574 ms
    assert decoder == pytest.approx(22.420, abs=1e-3)
    assert ms["repro.decoder.fetch"] == pytest.approx(10.942, abs=1e-3)
    assert ms["repro.executor.route"] == pytest.approx(10.164, abs=1e-3)
    window_ms = t.window_s * 1e3
    assert sp.idle_share(t, host, "repro.executor.") == pytest.approx(
        100 * executor / window_ms)
    assert sp.idle_share(t, host, "repro.decoder.") == pytest.approx(
        100 * decoder / window_ms)
    sweep = report.reader("device_idle_share.sweep")(
        SimpleNamespace(trace=t))
    assert sweep == pytest.approx(8.0503, abs=1e-4)
    assert 100 * (executor + decoder) / window_ms <= sweep


def test_span_stats_sum_over_the_window(recorded):
    t, host = recorded
    sums = sp.stat_sums(t, host)
    assert sums["repro.decoder.launch:decode_step"] == {
        "count": 2, "rows": 64, "padded_rows": 64, "tokens": 64,
        "padded_tokens": 64, "new_shape": 0, "pages_in_use": 107,
        "pages_reserved": 448}
    assert sums["repro.decoder.launch:prefill_into_pages"]["tokens"] == 2468
    assert sums["repro.executor.dispatch"] == {"count": 3, "routed": 32}
    assert sums["repro.decoder.pages"] == {"count": 3, "cow": 0}
    assert sums["repro.executor.route"]["count"] == 35
    hand = sp.stat_sums(hand_trace(), hand_spans())
    assert hand["repro.decoder.launch:decode_step"]["pages_in_use"] == 160
    assert hand["repro.executor.step"] == {"count": 2, "rows": 32, "step": 1}

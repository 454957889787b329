"""One CPU run of the harness per traffic mix, on the smoke preset: the
device check is skipped and the rest of a run is driven in full, down to
the result line's contract, and again with a fault planted in the timed
path, which must turn ``correct`` false.  (The numbers of a CPU run are no
device metrics; these tests read only the line's shape and its verdict.)"""
import copy
import json
import time

import pytest

import benchpath  # noqa: F401
from harness import cell, report, traffic as tr

# small enough for a test run: fewer requests in flight, no shape warm-up
QUICK = {"warmup_requests": 8, "outstanding": 8, "prefill_rows": [],
         "reference_requests": 6}
# the end-to-end metrics a cell of the closed loop reports
E2E = [("tokens_per_s", "tokens/s")]


def mixes():
    return sorted(p.stem for p in tr.TRAFFIC_DIR.glob("*.json"))


def bench_for(traffic):
    """BENCHMARK.json with a test cell serving ``traffic`` on smollm2's
    smoke preset."""
    bench = copy.deepcopy(cell.load_benchmark())
    name = f"test.{traffic}"
    bench["workloads"].append({"name": name, "config": "smollm2-1.7b",
                               "traffic": traffic, "chips": 1, "why": "-"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for metric, unit in E2E:
        entry = e2e.setdefault(metric, {"name": metric, "unit": unit,
                                        "workloads": []})
        entry.setdefault("workloads", []).append(name)
    bench["end_to_end"] = list(e2e.values())
    return bench, name


def smoke_run(traffic, seed=4_000_000_007, **kw):
    bench, name = bench_for(traffic)
    result = report.run_and_report(
        bench, name, seed, 3.0, trace_dir=None, process_start=time.time(),
        smoke=True, device_name="TPU v5e", mix_overrides=QUICK, **kw)
    return bench, name, result


def assert_contract(bench, name, result):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {m["name"] for m in report.cell_metrics(bench, "end_to_end",
                                                   name)}
    assert set(result["metrics"]) == want and "setup_s" in want
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1 and "kind" in dev
    assert "memory_peak_bytes" in dev
    for c in result["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(result, allow_nan=False)


@pytest.mark.parametrize("traffic", mixes())
def test_smoke_run_prints_the_contract(traffic, capsys):
    bench, name, result = smoke_run(traffic)
    assert_contract(bench, name, result)
    report.emit(result)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1])["correct"] is True
    assert err.strip().splitlines()[-1].startswith("[bench] check ")
    assert result["checks"]["shared_label_steps_wrong"]["value"] == 0


def alter_tokens(pool):
    """A token altered where it is produced: every served token of one
    request in three is replaced by its neighbour in the vocabulary."""
    inner = pool._inner
    V = pool.cfg.vocab_size

    def step(payloads, members):
        out = inner(payloads, members)
        return {rid: ((t + 1) % V if rid % 3 == 0 else t)
                for rid, t in out.items()}
    pool._inner = step


def prefix_cache_off(pool):
    """The decoder's prefix index finds nothing: every admission prefills
    its whole prompt, so the harness's shared-prefix labels no longer say
    what the decoder did."""
    inner = pool._inner

    def step(payloads, members):
        dec = payloads.get("_stream_decoder")
        if dec is not None:
            dec.prefix.lookup = lambda *_a, **_k: []
        return inner(payloads, members)
    pool._inner = step


def test_a_fault_in_the_timed_path_is_not_correct():
    _bench, _name, result = smoke_run("pff-sweep.shared-doc",
                                      break_path=alter_tokens)
    assert result["correct"] is False
    gaps = [result["checks"][k]["value"] for k in
            ("logit_gap_shared", "logit_gap_unshared")]
    assert max(gaps) > result["checks"]["logit_gap_shared"]["limit"]


def test_shared_labels_are_held_against_the_decoder():
    _bench, _name, result = smoke_run("pff-sweep.shared-doc",
                                      break_path=prefix_cache_off)
    assert result["correct"] is False
    assert result["checks"]["shared_label_steps_wrong"]["value"] > 0

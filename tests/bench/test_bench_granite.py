"""Granite-3.0-8B on the served path against its plain reference.

At the program's smoke preset of ``granite-3-8b``, with Granite's four
multipliers and in float32: prefill, then paged decode through the
``StreamingDecoder``, must give the logits that ``reference/granite.py``
computes from the same seed, and those of the program's own full-sequence
``forward``.  With any one multiplier reset to its default in the program,
the served logits must leave the reference by far more than the
tolerance.

At the published widths (cut to four layers, one token out per request)
the harness's own check of served tokens must read the float8 control,
and a reference that leaves out the residual or the attention multiplier
or the scaling of the embedding, as not correct.  Two departures cannot
move a greedy token and are left to the logit comparison: the logits
scaling, and the embedding multiplier reset together with the table it
draws (the table is drawn at 0.02 / multiplier, so the pair only rescales
the logits).
"""
import copy
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchpath  # noqa: F401
from harness import cell, correct, traffic as tr
from harness.served import PAGE, Outcome
from reference import granite
from repro.configs import get_config
from repro.inference.streaming import StreamingDecoder
from repro.models import model as M

SEED = 5
DECODE = 6
# f32 logits of ~0.3 through two layers of width 256: the served path
# reads 6e-8 from the reference; the smallest departure a dropped
# multiplier makes is ~1.7e-4 (the attention multiplier)
TOL = 1e-5
DEFAULTS = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
            "attention_multiplier": 0.0, "logits_scaling": 1.0}


def granite_config():
    with open(cell.BENCH_DIR / "configs" / "granite-3.0-8b.l10.json") as f:
        return json.load(f)


def prompts(vocab):
    """Two requests that share a two-page prefix, and one alone."""
    rng = np.random.default_rng(0)
    shared = [int(t) for t in rng.integers(4, vocab, 16)]
    tail = lambda n: [int(t) for t in rng.integers(4, vocab, n)]  # noqa
    return {0: shared + tail(7), 1: shared + tail(5), 2: tail(30)}


def serve(mc, reqs):
    """Each request's served tokens and the logits that chose them, from
    the decoder's paged admission and decode, in float32 with the weights
    rounded to bfloat16 as the reference draws them."""
    params = M.init_params(mc, jax.random.PRNGKey(SEED))
    params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    dec = StreamingDecoder(mc, params, None, None, prompt_len=48, max_len=64,
                           page_size=8, paged=True)
    logits = {r: [] for r in reqs}
    sample = dec._sample

    def keep(lg, picks):
        a = np.asarray(lg)
        for r, index in picks:
            logits[r].append(a[index])
        return sample(lg, picks)

    dec._sample = keep
    for r, p in reqs.items():
        dec.ensure_tokens(r, p)
    for _ in range(DECODE):
        dec.step(list(reqs))
    assert dec.shared_tokens_total == 16
    served = {r: dec._tokens[r][len(reqs[r]):] for r in reqs}
    return ({r: np.stack(v).astype(np.float32) for r, v in logits.items()},
            served, params)


@pytest.fixture(scope="module")
def smoke():
    mc, small = cell.smoke_config(granite_config())
    return mc.with_(dtype="float32"), small


def reference_gap(small, reqs, logits, served):
    ref = granite.served_logits(small, SEED, list(reqs.values()),
                                list(served.values()))["f32"]
    return max(float(np.abs(logits[r] - lg).max())
               for r, lg in zip(reqs, ref))


def test_config_file_is_the_registered_program():
    """``program_config`` checks the widths it knows; the multipliers,
    which the reference reads from the file, are held to the program's
    registered ``granite-3-8b`` here."""
    c = granite_config()
    mc = get_config(c["program_arch"])
    for key, field in (("embedding_multiplier", "embedding_multiplier"),
                       ("residual_multiplier", "residual_multiplier"),
                       ("attention_multiplier", "attention_multiplier"),
                       ("logits_scaling", "logits_scaling"),
                       ("hidden_size", "d_model"),
                       ("intermediate_size", "d_ff"),
                       ("num_attention_heads", "n_heads"),
                       ("num_key_value_heads", "n_kv_heads"),
                       ("head_dim", "resolved_head_dim"),
                       ("vocab_size", "vocab_size")):
        assert c[key] == getattr(mc, field), key
    assert (mc.n_layers, c["num_hidden_layers"]) == (40, 10)
    assert list(c["reduced"]) == ["num_hidden_layers"]
    served = cell.program_config(c)
    assert served.n_layers == 10 and served.logits_scaling == 16.0


def test_served_path_agrees_with_reference_and_forward(smoke):
    mc, small = smoke
    reqs = prompts(mc.vocab_size)
    logits, served, params = serve(mc, reqs)
    assert all(len(s) == DECODE for s in served.values())
    assert reference_gap(small, reqs, logits, served) < TOL
    for r, p in reqs.items():
        seq = jnp.asarray([p + served[r][:-1]], jnp.int32)
        full = np.asarray(M.forward(mc, params, {"tokens": seq}))[0]
        np.testing.assert_allclose(logits[r], full[len(p) - 1:], atol=TOL,
                                   rtol=0)


@pytest.mark.parametrize("dropped", list(DEFAULTS))
def test_a_dropped_multiplier_leaves_the_reference(smoke, dropped):
    mc, small = smoke
    reqs = prompts(mc.vocab_size)
    logits, served, _ = serve(mc.with_(**{dropped: DEFAULTS[dropped]}), reqs)
    assert reference_gap(small, reqs, logits, served) > 10 * TOL


def test_the_configuration_serves_through_the_harness():
    """A CPU run of the harness's served path (``Gateway`` ->
    ``LiveExecutor`` -> ``StreamingDecoder``) on the smoke preset, with the
    traffic drafted for Granite's cell: every finished request gets its
    tokens, and cohorts map their shared evidence pages."""
    bench = copy.deepcopy(cell.load_benchmark())
    bench["configs"].append({"name": "granite-3.0-8b.l10",
                             "file": "bench/configs/granite-3.0-8b.l10.json"})
    bench["workloads"].append({"name": "test.granite",
                               "config": "granite-3.0-8b.l10",
                               "traffic": "pff-sweep.shared-doc-32x384"})
    quick = {"warmup_requests": 8, "outstanding": 8, "prefill_rows": []}
    run = cell.run_cell("test.granite", 4_000_000_007, 3.0, trace_dir=None,
                        process_start=time.time(), smoke=True,
                        device_name="TPU v5e", mix_overrides=quick,
                        bench=bench)
    done = [o for o in run.window.outcomes if run.results[o.rid]]
    assert done
    for o in done:
        toks = run.results[o.rid]
        assert len(toks) == o.spec.decode_tokens
        assert all(0 <= t < run.cfg["vocab_size"] for t in toks)
    assert any(o.shared_base > 0 for o in done)


FULL_LAYERS = 4
FULL_PROMPT = 96


@pytest.fixture(scope="module")
def published_widths():
    """The configuration cut to ``FULL_LAYERS``, and sixteen requests of
    eight evidence groups (the first of each admitted alone, the second
    finding its pages), each cut to its own length so that no two predict
    from the same context, with one token out."""
    cfg = dict(granite_config(), num_hidden_layers=FULL_LAYERS)
    mix = tr.load_mix("pff-sweep.shared-doc-32x384")
    t = tr.Traffic(mix, SEED, cfg["vocab_size"])
    picked = []
    for j in range(16):
        spec = t.request(32 * (j // 2) + j % 2)
        spec = dataclasses.replace(spec, tokens=spec.tokens[:FULL_PROMPT + j],
                                   decode_tokens=1)
        picked.append(Outcome(spec, rid=100 + j, due=0.0,
                              shared_base=PAGE * (j % 2)))
    return cfg, picked


def first_tokens_checked(cfg, picked, served_cfg=None, precision="f32"):
    """``correct.check`` of the tokens that the reference, computed from
    ``served_cfg`` in ``precision``, puts first."""
    out = granite.served_logits(served_cfg or cfg, SEED,
                                [o.spec.tokens for o in picked],
                                [[0]] * len(picked), (precision,))
    results = {o.rid: [int(lg[0].argmax())]
               for o, lg in zip(picked, out[precision])}
    checks, info = correct.check(cfg, SEED, picked, results)
    assert info["shared_requests"] == info["unshared_requests"] == 8
    return checks


def test_published_widths_reference_tokens_are_correct(published_widths):
    checks = first_tokens_checked(*published_widths)
    assert correct.passed(checks), checks


@pytest.mark.parametrize("served_by", ["fp8", "residual_multiplier",
                                       "attention_multiplier",
                                       "embedding_multiplier"])
def test_published_widths_departure_is_not_correct(published_widths,
                                                   served_by, monkeypatch):
    cfg, picked = published_widths
    if served_by == "fp8":
        checks = first_tokens_checked(cfg, picked, precision="fp8")
    else:
        if served_by == "embedding_multiplier":
            # the same table, its rows no longer scaled
            draw = granite._embed_weights
            monkeypatch.setattr(granite, "_embed_weights",
                                lambda key, sizes, _m: draw(
                                    key, sizes, cfg["embedding_multiplier"]))
        checks = first_tokens_checked(
            cfg, picked, dict(cfg, **{served_by: DEFAULTS[served_by]}))
    assert checks["served_tokens_wrong"]["value"] == 0
    assert correct.passed(checks) is False, checks

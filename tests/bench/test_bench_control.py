"""The control: the reference itself, computed with float8 operands, put in
the program's place.  At a size a test run holds (four layers at the
published widths and vocabulary, prompts cut to 96 tokens), the tokens it
puts first, handed to the harness's own comparison as the served tokens of
a sample with shared-prefix and unshared admissions, must make the run not
correct."""
import dataclasses

import benchpath  # noqa: F401
from harness import cell, correct, traffic as tr
from harness.served import PAGE, Outcome
from reference import dense_gqa

PROMPT = 96
SEED = 11


def sample():
    """Eight requests of two evidence groups, cut to ``PROMPT`` tokens: the
    first of each group is admitted alone, the others find its first page
    resident."""
    mix = tr.load_mix("pff-sweep.shared-doc")
    t = tr.Traffic(mix, SEED, 49152)
    picked = []
    for i in (0, 1, 2, 3, 16, 17, 18, 19):
        spec = t.request(i)
        spec = dataclasses.replace(spec, tokens=spec.tokens[:PROMPT])
        first = i % mix["docs_shared_by"] == 0
        picked.append(Outcome(spec, rid=100 + i, due=0.0,
                              shared_base=0 if first else PAGE))
    return picked


def test_float8_control_is_not_correct():
    bench = cell.load_benchmark()
    cfg = dict(cell.load_config(bench, "smollm2-1.7b"), num_hidden_layers=4)
    picked = sample()
    assert picked[1].spec.tokens[:PAGE] == picked[0].spec.tokens[:PAGE]
    n = picked[0].spec.decode_tokens
    placeholder = [[1] * n for _ in picked]
    out = dense_gqa.served_logits(cfg, SEED, [o.spec.tokens for o in picked],
                                  placeholder, ("fp8",))
    # the tokens the control puts first, served in the program's place
    results = {o.rid: [int(t) for t in lg.argmax(-1)]
               for o, lg in zip(picked, out["fp8"])}
    checks, info = correct.check(cfg, SEED, picked, results)
    assert info["shared_requests"] == 6 and info["unshared_requests"] == 2
    assert checks["served_tokens_wrong"]["value"] == 0
    gaps = [checks[k]["value"] for k in ("logit_gap_shared",
                                         "logit_gap_unshared")]
    assert max(gaps) > cfg["correctness"]["logit_gap_limit"], checks
    assert correct.passed(checks) is False

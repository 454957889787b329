"""The benchmark's copies of the program's data path give what the program
gives, and the traffic is fixed by its seed."""
import dataclasses

import benchpath  # noqa: F401
from harness import traffic as tr

from repro.data.claims import generate_claims
from repro.data.prompts import TEMPLATES
from repro.data.tokenizer import ByteTokenizer


def test_claim_texts_equal_the_program_generator():
    assert tr.claim_texts(500, seed=0) == \
        [c.text for c in generate_claims(500, seed=0)]


def test_rendering_and_tokens_equal_the_program():
    mix = tr.load_mix("pff-sweep.shared-doc")
    t = tr.Traffic(mix, seed=2 ** 33 + 5, vocab_size=49152)
    tok = ByteTokenizer(49152)
    for i in (0, 1, 17, 300):
        spec = t.request(i)
        c = generate_claims(1, seed=0)[0]
        claim = dataclasses.replace(c, text=spec.claim.text,
                                    evidence=spec.claim.evidence)
        want = tok.encode(TEMPLATES["with_evidence"].render(claim))
        assert list(spec.tokens) == want


def test_same_seed_same_requests_and_same_work_for_any_seed():
    mix = tr.load_mix("pff-sweep.shared-doc")
    a = tr.Traffic(mix, seed=4_000_000_007, vocab_size=49152)
    b = tr.Traffic(mix, seed=4_000_000_007, vocab_size=49152)
    c = tr.Traffic(mix, seed=11, vocab_size=49152)
    assert [a.request(i).tokens for i in range(40)] == \
        [b.request(i).tokens for i in range(40)]
    # another seed: another order of the same claim texts, other documents
    assert sorted(a._texts) == sorted(c._texts) and a._texts != c._texts
    assert a.request(0).claim.evidence != c.request(0).claim.evidence
    assert a.prompt_lengths() == c.prompt_lengths()
    assert {a.request(i).slo for i in range(8)} == {"batch"}


def test_groups_share_whole_evidence_pages():
    mix = tr.load_mix("pff-sweep.shared-doc")
    t = tr.Traffic(mix, seed=3, vocab_size=49152)
    a, b, c = t.request(0), t.request(15), t.request(16)
    assert a.tokens[:256] == b.tokens[:256]         # 4 pages of 64
    assert a.tokens[:64] != c.tokens[:64]           # next group: new doc
    lo, hi = t.prompt_lengths()
    assert hi <= mix["prompt_len"]
    buckets = tr.prefill_buckets(mix, lo, hi, 64)
    assert all(b % 8 == 0 for b in buckets)
    assert -(-hi // 8) * 8 in buckets and -(-(lo - 256) // 8) * 8 in buckets

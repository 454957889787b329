"""The benchmark's one traffic generator: Prompt-for-Fact requests.

A traffic mix is a JSON file of parameters in ``bench/traffic/`` (see
``load_mix``); this module turns it and ``--seed`` into the run's requests.
Nothing here imports the program: the claim generator, the prompt template
and the tokenizer are the benchmark's own copies of ``repro.data``, so the
reference tokenizes what the program should have served on its own, and a
later change to the program cannot change the traffic.

Steadiness: every seed gets the SAME multiset of claim texts, in another
order.  The seed changes the order, the
evidence documents and the weights, never the amount of work.
"""
from __future__ import annotations

import json
import math
import pathlib
import random
from dataclasses import dataclass
from typing import Dict, List, Tuple

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parents[1] / "traffic"

CLAIM_SET_SEED = 0        # the fixed multiset of claim texts
DOC_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789",
                             np.uint8)

# -- copy of repro.data.claims (the FEVER-like generator) -------------------
_CITIES = ["Paris", "Tokyo", "Lagos", "Lima", "Oslo", "Cairo", "Quito",
           "Hanoi", "Accra", "Sofia", "Turin", "Kyoto", "Davao", "Bergen"]
_COUNTRIES = ["France", "Japan", "Nigeria", "Peru", "Norway", "Egypt",
              "Ecuador", "Vietnam", "Ghana", "Bulgaria", "Italy"]
_NAMES = ["Ada Obi", "Kenji Sato", "Maria Silva", "Lars Berg", "Nadia Riad",
          "Pablo Cruz", "Linh Tran", "Kofi Mensah", "Elena Petrova",
          "Luca Romano", "Aya Tanaka", "Rosa Flores"]


@dataclass(frozen=True)
class Claim:
    """A claim with its evidence; the program's templates read ``text`` and
    ``evidence``."""
    claim_id: int
    text: str
    evidence: str
    label: str


def _sentence(entity: str, relation: str, value: str) -> str:
    if relation == "capital":
        return f"{value} is the capital of {entity}"
    if relation == "born":
        return f"{entity} was born in {value}"
    if relation == "population":
        return f"the population of {entity} is {value}"
    return f"{entity} {relation} {value}"


def claim_texts(n: int, seed: int = CLAIM_SET_SEED,
                empty_fraction: float = 0.003) -> List[str]:
    """The claim texts of ``repro.data.claims.generate_claims(n, seed)``."""
    rng = random.Random(seed)
    frng = random.Random(seed)
    facts = []
    for c in _COUNTRIES:
        facts.append((c, "capital", frng.choice(_CITIES)))
        facts.append((c, "population", str(frng.randint(1, 200)) + " million"))
    for name in _NAMES:
        facts.append((name, "born", str(frng.randint(1900, 2005))))
    out: List[str] = []
    for _ in range(n):
        if rng.random() < empty_fraction:
            out.append("")
            continue
        ent, rel, val = rng.choice(facts)
        roll = rng.random()
        if roll < 1 / 3:
            out.append(_sentence(ent, rel, val))
        elif roll < 2 / 3:
            if rel == "capital":
                alt = rng.choice([c for c in _CITIES if c != val])
            elif rel == "born":
                alt = str(int(val) + rng.randint(1, 50))
            else:
                alt = val + " thousand"
            out.append(_sentence(ent, rel, alt))
        else:
            ghost = ("the lost city of " + rng.choice(_CITIES) + "-"
                     + str(rng.randint(2, 99)))
            out.append(_sentence(ghost, rel, val))
    return out


# -- copy of repro.data.prompts "with_evidence" and the byte tokenizer -------
def render_with_evidence(claim: Claim) -> str:
    return (f"evidence {claim.evidence} . claim {claim.text} . is the claim "
            f"supported refuted or not enough info . answer")


TEMPLATES = {"with_evidence": render_with_evidence}

BOS = 1
_N_SPECIAL = 8
_WORDS = [
    "the", "a", "is", "was", "of", "in", "to", "and", "claim", "true",
    "false", "evidence", "supported", "refuted", "not", "enough", "info",
    "verify", "fact", "statement", "answer", "label", "wikipedia", "born",
    "year", "city", "country", "film", "directed", "by", "released",
    "population", "capital", "author", "wrote", "album", "band", "played",
]


def encode(text: str, vocab_size: int) -> List[int]:
    """Token ids of ``text`` as the byte tokenizer gives them (BOS first)."""
    need = _N_SPECIAL + len(_WORDS) + 256
    words = _WORDS if vocab_size >= need else \
        _WORDS[:max(0, vocab_size - _N_SPECIAL - 256)]
    word_id = {w: _N_SPECIAL + i for i, w in enumerate(words)}
    byte_base = _N_SPECIAL + len(words)
    ids = [BOS]
    for tok in text.split(" "):
        wid = word_id.get(tok)
        if wid is not None:
            ids.append(wid)
        else:
            ids.extend(byte_base + b for b in tok.encode("utf-8"))
        ids.append(byte_base + ord(" "))
    if text:
        ids.pop()
    return ids


# -- the mix ----------------------------------------------------------------
@dataclass
class RequestSpec:
    """One request of the run, as the benchmark generated it."""
    index: int
    claim: Claim
    slo: str
    decode_tokens: int
    group: int
    tokens: Tuple[int, ...]        # the prompt as the reference tokenizes it


def load_mix(name: str) -> dict:
    """The traffic file ``bench/traffic/<name>.json``."""
    path = TRAFFIC_DIR / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be 'closed'")
    return mix


class Traffic:
    """The requests of one run: ``mix`` parameters, ``seed`` order."""

    def __init__(self, mix: dict, seed: int, vocab_size: int):
        self.mix = mix
        self.seed = seed
        self.vocab_size = vocab_size
        self.render = TEMPLATES[mix["template"]]
        texts = claim_texts(mix["claim_set"])
        order = np.random.default_rng([seed, 0]).permutation(len(texts))
        self._texts = [texts[i] for i in order]
        self._docs: Dict[int, str] = {}
        self._specs: Dict[int, RequestSpec] = {}

    # evidence: one seeded document per group of `docs_shared_by` requests
    def _doc(self, group: int) -> str:
        doc = self._docs.get(group)
        if doc is None:
            rng = np.random.default_rng([self.seed, 1, group])
            idx = rng.integers(0, len(DOC_ALPHABET), self.mix["doc_bytes"])
            doc = DOC_ALPHABET[idx].tobytes().decode("ascii")
            self._docs[group] = doc
        return doc

    def _class_of(self, i: int) -> dict:
        for cls in self.mix["classes"]:
            every = cls.get("every", 1)
            if i % every == 0:
                return cls
        raise ValueError("traffic classes must end with one of every 1")

    def request(self, i: int) -> RequestSpec:
        """The ``i``-th request of the run (deterministic in the seed)."""
        spec = self._specs.get(i)
        if spec is None:
            group = i // self.mix["docs_shared_by"]
            claim = Claim(i, self._texts[i % len(self._texts)],
                          self._doc(group), "")
            cls = self._class_of(i)
            toks = tuple(encode(self.render(claim), self.vocab_size))
            spec = RequestSpec(i, claim, cls["slo"], cls["decode_tokens"],
                               group, toks)
            self._specs[i] = spec
        return spec

    def prompt_lengths(self) -> Tuple[int, int]:
        """(shortest, longest) prompt this mix can send, in tokens."""
        lens = [len(encode(self.render(Claim(0, t, "x" * self.mix["doc_bytes"],
                                             "")), self.vocab_size))
                for t in set(self._texts)]
        return min(lens), max(lens)


def prefill_buckets(mix: dict, lo: int, hi: int, page: int) -> List[int]:
    """Every admission-prefill token bucket a batch of this mix can have:
    a row prefills its whole prompt (no shared pages) or the tail past a
    page-aligned shared prefix: its evidence's whole pages, or more where
    two requests of a group carry the same claim text.  The decoder pads
    the longest row of a batch up to a multiple of 8."""
    doc_pages = (3 + mix["doc_bytes"]) // page if mix["docs_shared_by"] > 1 \
        else 0
    lens = set()
    for n in range(lo, hi + 1):
        lens.add(n)
        if doc_pages:
            for pages in range(doc_pages, (n - 1) // page + 1):
                lens.add(n - pages * page)
    return sorted({int(math.ceil(n / 8) * 8) for n in lens})

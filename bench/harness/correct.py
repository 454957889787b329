"""Whether the timed path served what the plain reference computes.

After the window has closed, ``memory_peak_bytes`` has been read and the
program's state is freed, a sample of the requests the window finished is
drawn from the seed: the longest of them, and as many requests whose
admission mapped shared prefix pages (a tail-only prefill) as requests
whose admission mapped none.  The reference runs once over each prompt with
the tokens the program served.  For every served token the number compared
is how far its reference logit lies below the reference's best logit at
that position (0 where the served token is the reference's greedy choice);
the widest such gap over the sample has to stay under the configuration's
limit.  A served token that is missing or outside the vocabulary fails.

Which admissions mapped shared pages is the harness's reading of the
decoder's prefix rule (``ServedPool._shared_base``).  It is held against
the decoder's own counter on every step of the window: a step whose
admissions' shared bases do not sum to the decoder's ``shared_tokens_total``
delta fails the run, since the split it draws the sample by would then be
unfounded.
"""
from __future__ import annotations

import importlib
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np


def sample(outcomes: Sequence, results: Dict[int, list], seed: int,
           n: int) -> List:
    """Finished requests to compare: the longest, then shared-prefix and
    unshared admissions in equal parts where both exist, drawn from the
    seed."""
    done = [o for o in outcomes if len(results.get(o.rid, ())) > 0]
    if not done:
        return []
    rng = np.random.default_rng([seed, 3])
    longest = max(done, key=lambda o: (len(o.spec.tokens)
                                       + o.spec.decode_tokens, -o.rid))
    pick = [longest]
    shared = [o for o in done if o.shared_base > 0 and o is not longest]
    unshared = [o for o in done if o.shared_base == 0 and o is not longest]
    half = (n - 1) // 2
    for group, k in ((shared, half), (unshared, n - 1 - half)):
        if group:
            idx = rng.choice(len(group), size=min(k, len(group)),
                             replace=False)
            pick += [group[i] for i in sorted(idx)]
    rest = [o for o in done if o not in pick]
    if len(pick) < n and rest:
        idx = rng.choice(len(rest), size=min(n - len(pick), len(rest)),
                         replace=False)
        pick += [rest[i] for i in sorted(idx)]
    return pick


def gaps(logits: np.ndarray, served: Sequence[int]) -> np.ndarray:
    """Per position: best reference logit minus the served token's."""
    served = np.asarray(served)
    best = logits.max(axis=-1)
    return best - logits[np.arange(len(served)), served]


def reference_module(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}")


def check(cfg: dict, weight_seed: int, picked: Sequence,
          results: Dict[int, list], steps: Sequence = ()
          ) -> Tuple[Dict[str, dict], dict]:
    """The numbers compared, each with its limit, and what they came from.
    ``steps`` are the window's step records, whose shared-prefix labels
    are held against the decoder's counter."""
    limit = cfg["correctness"]["logit_gap_limit"]
    V = cfg["vocab_size"]
    bad_tokens = 0
    prompts, served, kinds = [], [], []
    for o in picked:
        toks = list(results[o.rid])
        if len(toks) != o.spec.decode_tokens or \
                any(not 0 <= t < V for t in toks):
            bad_tokens += 1
            continue
        prompts.append(o.spec.tokens)
        served.append(toks)
        kinds.append("shared" if o.shared_base > 0 else "unshared")
    widest = {"shared": 0.0, "unshared": 0.0}
    if prompts:
        ref = reference_module(cfg).served_logits(
            cfg, weight_seed, prompts, served)["f32"]
        for lg, s, kind in zip(ref, served, kinds):
            g = float(gaps(lg, s).max())
            widest[kind] = max(widest[kind], g)
    checks = {
        "served_tokens_wrong": {"value": bad_tokens, "limit": 0},
        "shared_label_steps_wrong": {
            "value": sum(not s.labels_agree for s in steps), "limit": 0},
        "logit_gap_shared": {"value": widest["shared"], "limit": limit},
        "logit_gap_unshared": {"value": widest["unshared"], "limit": limit},
    }
    info = {"compared_requests": len(prompts),
            "compared_tokens": int(sum(len(s) for s in served)),
            "shared_requests": kinds.count("shared"),
            "unshared_requests": kinds.count("unshared")}
    return checks, info


def passed(checks: Dict[str, dict]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())

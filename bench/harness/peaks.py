"""Published peaks of each accelerator, one file per device kind in
``bench/peaks/``, keyed by ``device_kind`` as JAX reports it.  A device that
has no file is an error, never a default."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

PEAKS_DIR = pathlib.Path(__file__).resolve().parents[1] / "peaks"


def table() -> Dict[str, dict]:
    out = {}
    for path in sorted(PEAKS_DIR.glob("*.json")):
        with open(path) as f:
            entry = json.load(f)
        out[entry["device_kind"]] = entry
    return out


def peaks(device_kind: str) -> dict:
    t = table()
    if device_kind not in t:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (have {sorted(t)})")
    return t[device_kind]

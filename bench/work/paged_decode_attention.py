"""The work of the paged decode attention kernel, from its shapes.

Per call (one layer of one decode step) over the rows that decode: read Q
(B x H x hd), the valid K and V tokens of every row (n_valid x K x hd each),
write the output (B x H x hd); 4 * H * hd FLOPs per valid token per row
(scores and weighted values).  This is the algorithm's work: lane padding
of the page pool, idle pool rows and positions past n_valid are not
counted, so the same work is read whatever implements it.
"""
from __future__ import annotations

from typing import Sequence, Tuple


def call_work(n_valid: Sequence[int], n_heads: int, n_kv_heads: int,
              head_dim: int, itemsize: int = 2) -> Tuple[int, int]:
    """(flops, bytes) of one call over rows with context ``n_valid``."""
    B = len(n_valid)
    tokens = sum(n_valid)
    flops = 4 * n_heads * head_dim * tokens
    q_out = 2 * B * n_heads * head_dim * itemsize
    kv = 2 * tokens * n_kv_heads * head_dim * itemsize
    return flops, q_out + kv


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bytes_per_s: float) -> Tuple[float, str]:
    """Least time the chip needs for the work, and which peak bounds it."""
    t_f, t_b = flops / peak_flops, nbytes / peak_bytes_per_s
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")

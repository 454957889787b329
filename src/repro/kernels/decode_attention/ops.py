"""Decode attention (dense + paged): the Pallas kernel in a program compiled
for TPU, the pure-jnp reference elsewhere (see ``kernels/platform.py``)."""
from __future__ import annotations

import functools

from ..platform import tpu_kernel_else_ref
from .decode_attention import (decode_attention_paged_pallas,
                               decode_attention_pallas)
from .ref import decode_attention_paged_ref, decode_attention_ref


def decode_attention(q, k, v, n_valid, *, softcap: float = 0.0,
                     scale: float | None = None):
    """q: (B,1,H,hd); k,v ring cache (B,T,K,hd); n_valid int32 scalar or
    (B,) vector (per-row valid length — slot-pool decode)."""
    kw = dict(softcap=softcap, scale=scale)
    return tpu_kernel_else_ref(
        functools.partial(decode_attention_pallas, **kw),
        functools.partial(decode_attention_ref, **kw), q, k, v, n_valid)


def decode_attention_paged(q, k_pages, v_pages, page_table, n_valid, *,
                           softcap: float = 0.0, scale: float | None = None):
    """Paged decode attention: q (B,1,H,hd); k_pages/v_pages physical pools
    (n_pages,P,K,hd); page_table (B,max_pages) int32 (clamped >= 0, unmapped
    entries alias the trash page and sit past n_valid); n_valid int32 scalar
    or (B,) per-row valid length over the LOGICAL ring (max_pages*P slots)."""
    kw = dict(softcap=softcap, scale=scale)
    return tpu_kernel_else_ref(
        functools.partial(decode_attention_paged_pallas, **kw),
        functools.partial(decode_attention_paged_ref, **kw),
        q, k_pages, v_pages, page_table, n_valid)

"""Flash attention: the Pallas kernel in a program compiled for TPU, the
pure-jnp reference elsewhere (see ``kernels/platform.py``).

The dry-run compiles for CPU and so lowers the reference path on purpose:
``cost_analysis()`` needs the XLA-visible FLOPs, and custom-call kernels are
opaque to it.
"""
from __future__ import annotations

import functools

from ..platform import tpu_kernel_else_ref
from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, scale: float | None = None):
    """Entry point used by the model code.

    q: (B,S,H,hd); k,v: (B,T,K,hd); H = G*K. Sliding ``window`` and
    ``softcap`` are static. Returns (B,S,H,hd) in q.dtype.
    """
    kw = dict(causal=causal, window=window, softcap=softcap, scale=scale)
    return tpu_kernel_else_ref(functools.partial(flash_attention_pallas, **kw),
                               functools.partial(flash_attention_ref, **kw),
                               q, k, v)

"""Flash attention Pallas TPU kernel (GQA, causal, sliding window, softcap).

Online-softmax flash attention tiled for VMEM/MXU:

- grid = (B, H, S/bq, T/bk); the KV-block axis is the innermost sequential
  dimension, with fp32 scratch accumulators (m, l, acc) carried across it.
- q/k/v tiles are bq = bk = 128 rows; a length that 128 does not divide
  is taken as one whole block (a block equal to the array dim is always a
  legal TPU tile).  head_dim is never padded: the tile's lane dim is the
  full head_dim.
- GQA is expressed in the BlockSpec index maps: the k/v tile for q-head h is
  kv-head h // group_size — no repeated KV is ever materialised in VMEM.
- causal + sliding-window blocks that are fully masked are skipped via
  ``pl.when`` on block indices (no MXU work, no VMEM loads beyond the tile
  prefetch).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, softcap: float,
            bq: int, bk: int, n_kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # block-level skip: block is live iff some (qpos, kpos) pair is unmasked
    live = True
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # (bq, bk)
        correction = jnp.exp(m_prev - m_new)          # (bq, 1)
        l_scr[...] = l_scr[...] * correction + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * correction +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, scale: float | None = None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q: (B,S,H,hd); k: (B,T,K,hd); v: (B,T,K,hd_v) — hd_v may differ (MLA).
    A length that ``bq``/``bk`` does not divide is tiled as one block."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bq = S if S % min(bq, S) else min(bq, S)
    bk = T if T % min(bk, T) else min(bk, T)
    n_kv_blocks = T // bk

    # layout: (B, H, S, hd) so the lane dim is hd and sublane is seq
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, S // bq, n_kv_blocks)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, n_kv_blocks=n_kv_blocks)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd_v), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd_v), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd_v), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum
            pltpu.VMEM((bq, hd_v), jnp.float32), # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

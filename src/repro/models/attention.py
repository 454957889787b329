"""Attention variants: GQA (optionally qk-norm / sliding-window), MLA, cross.

Dense compute goes through the kernel wrappers in ``repro.kernels`` which
dispatch to the Pallas TPU kernels on TPU and to the pure-jnp reference on
CPU (and in the dry-run).

Shapes:  x (B,S,D); q (B,S,H,hd); k,v (B,T,K,hd) with H = G*K (GQA).
KV caches are ring buffers of length T = min(window or max_len, max_len);
slot(pos) = pos % T; K is stored *post-RoPE* so ring eviction needs no
re-rotation.  Decode positions are PER-ROW: ``pos`` may be a (B,) vector
(scalar broadcasts), each row ring-writing at its own slot and masking at
its own length — what lets a persistent slot pool decode a ragged dynamic
batch in lock-step.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_attention import ops as flash_ops
from ..kernels.decode_attention import ops as decode_ops
from .layers import apply_rope, rmsnorm, rmsnorm_init, trunc_normal


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg, dtype):
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": trunc_normal(ks[0], (D, H, hd), dtype=dtype),
        "wk": trunc_normal(ks[1], (D, K, hd), dtype=dtype),
        "wv": trunc_normal(ks[2], (D, K, hd), dtype=dtype),
        "wo": trunc_normal(ks[3], (H, hd, D), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def gqa_project_qkv(p, cfg, x, positions):
    """Project and rope q/k/v for a full sequence. positions: (S,) or (B,S)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_scale(cfg, hd: int) -> float:
    """The softmax scale for heads of size ``hd``: the configuration's
    attention multiplier, or 1/sqrt(hd) where it is 0."""
    return cfg.attention_multiplier or 1.0 / math.sqrt(hd)


def gqa_apply(p, cfg, x, *, positions=None, causal: bool = True):
    """Full-sequence attention (train / prefill)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    out = flash_ops.flash_attention(
        q, k, v, causal=causal, window=cfg.attn_window,
        softcap=cfg.attn_logit_softcap, scale=attn_scale(cfg, q.shape[-1]))
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def gqa_cache_len(cfg, max_len: int) -> int:
    return min(cfg.attn_window, max_len) if cfg.attn_window else max_len


def _quantize_kv(x):
    """(..., hd) -> int8 values + per-row f16 scale (§Perf G5)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale[..., 0].astype(jnp.float16)


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def gqa_cache_init(cfg, batch: int, max_len: int, n_layers: int, dtype):
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    T = gqa_cache_len(cfg, max_len)
    shape = (n_layers, batch, T, K, hd) if n_layers else (batch, T, K, hd)
    if cfg.kv_cache_dtype == "int8":       # §Perf G5
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], jnp.float16),
                "v_scale": jnp.zeros(shape[:-1], jnp.float16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_empty_cache_layer(cfg, batch: int, max_len: int, dtype):
    """One layer's empty ring cache (prefill writes into this)."""
    return gqa_cache_init(cfg, batch, max_len, 0, dtype)


def gqa_cache_write_prefill(cache_layer, cfg, k, v, max_len: int):
    """Write a prefill's K/V (B,S,K,hd) into one layer's ring cache (B,T,K,hd)."""
    T = cache_layer["k"].shape[1]
    S = k.shape[1]
    if S > T:
        # keep the last T positions, placed at their ring slots
        slots = (jnp.arange(S - T, S, dtype=jnp.int32) % T)
        order = jnp.argsort(slots)
        k = jnp.take(k[:, S - T:], order, axis=1)
        v = jnp.take(v[:, S - T:], order, axis=1)

    def upd(c, val):
        return jax.lax.dynamic_update_slice_in_dim(c, val, 0, axis=1)

    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": upd(cache_layer["k"], kq),
                "v": upd(cache_layer["v"], vq),
                "k_scale": upd(cache_layer["k_scale"], ks),
                "v_scale": upd(cache_layer["v_scale"], vs)}
    return {"k": upd(cache_layer["k"], k), "v": upd(cache_layer["v"], v)}


def gqa_cache_write_decode(cache_layer, cfg, k, v, slots):
    """Ring-write one decode token's K/V (B,1,K,hd) at PER-ROW ``slots``
    (B,) of one layer's cache (B,T,K,hd) — a batched scatter, so every row
    of a persistent slot pool advances at its own ring position."""
    B = k.shape[0]
    rows = jnp.arange(B)

    def upd(c, val):
        return c.at[rows, slots].set(val[:, 0])

    if cfg.kv_cache_dtype == "int8":       # §Perf G5
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        return {"k": upd(cache_layer["k"], kq),
                "v": upd(cache_layer["v"], vq),
                "k_scale": upd(cache_layer["k_scale"], ks),
                "v_scale": upd(cache_layer["v_scale"], vs)}
    return {"k": upd(cache_layer["k"], k), "v": upd(cache_layer["v"], v)}


# ---------------------------------------------------------------------------
# Paged GQA cache (refcounted shared-prefix pages)
# ---------------------------------------------------------------------------
#
# The paged layout replaces each layer's dense per-row ring (B, T, K, hd)
# with a physical page POOL (n_pages, P, K, hd) addressed through a per-row
# int32 page table (B, max_pages): row b's logical ring slot s lives at
# ``pool[table[b, s // P], s % P]``, so two rows whose tables map the same
# physical page SHARE those K/V bytes (a common prompt prefix is prefilled
# once and refcounted, never copied).  Physical page 0 is reserved as the
# TRASH page: unmapped table entries and masked lock-step writes land there
# and are never attended (always past a row's n_valid).

def gqa_paged_cache_init(cfg, n_pages: int, page_size: int, n_layers: int,
                         dtype):
    """One stage's paged KV pool: leaves (L, n_pages, P, K, hd)."""
    assert cfg.kv_cache_dtype != "int8", \
        "paged KV does not support int8 cache quantisation"
    K, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    shape = (n_layers, n_pages, page_size, K, hd) if n_layers else \
        (n_pages, page_size, K, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def gqa_cache_write_decode_paged(cache_layer, cfg, k, v, pages, offsets):
    """Scatter one decode token's K/V (B,1,K,hd) at page-offset coordinates
    — ``pages``/``offsets`` (B,) physical coords into one layer's pool
    (n_pages, P, K, hd).  Masked/idle rows are routed to the trash page by
    the caller, so lock-step junk writes can never touch a live page."""
    def upd(c, val):
        return c.at[pages, offsets].set(val[:, 0])

    return {"k": upd(cache_layer["k"], k), "v": upd(cache_layer["v"], v)}


def gqa_decode_paged(p, cfg, x, cache_layer, table, pos, write_mask=None):
    """One-token decode for one layer through a page table.

    x: (B,1,D); table: (B, max_pages) int32 physical page ids (<= 0 =
    unmapped → trash); pos: (B,) or scalar tokens-already-in-context.
    Returns (out, new_cache_layer).  The ring length is max_pages * P; a
    write whose ring slot falls in an unmapped logical page goes to trash
    (the host allocator maps a real page before any live row's write).
    ``write_mask`` (B,) bool routes idle rows' lock-step writes to trash
    too — unlike the contiguous ring, an idle row's slot may sit in a
    REFCOUNT-SHARED page, where a junk write would corrupt the page for
    its other holders instead of self-healing."""
    B = x.shape[0]
    P = cache_layer["k"].shape[1]
    max_pages = table.shape[1]
    T = max_pages * P
    pos = decode_positions(pos, B)
    q, k, v = gqa_project_qkv(p, cfg, x, pos[:, None])
    slot = pos % T
    logical = slot // P
    phys = jnp.take_along_axis(jnp.maximum(table, 0), logical[:, None],
                               axis=1)[:, 0]
    if write_mask is not None:
        phys = jnp.where(write_mask, phys, 0)
    new_cache = gqa_cache_write_decode_paged(cache_layer, cfg, k, v,
                                             phys, slot % P)
    n_valid = jnp.minimum(pos + 1, T)
    out = decode_ops.decode_attention_paged(
        q, new_cache["k"], new_cache["v"], table, n_valid,
        softcap=cfg.attn_logit_softcap, scale=attn_scale(cfg, q.shape[-1]))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


def gqa_prefill_into_pages(p, cfg, x, cache_layer, table, positions,
                           lengths):
    """Tail prefill THROUGH the page table for one layer.

    x: (Bn,S,D) normed tail hidden states; table: (Bn, max_pages) the
    admitted rows' physical page maps; positions: (Bn,S) absolute token
    positions (``base + t`` — base is the shared-prefix length already in
    pages, so the tail K/V ring-writes land right after the shared span);
    lengths: (Bn,) true tail lengths (padding positions write to trash).

    Tail queries attend over the row's WHOLE mapped ring — the refcounted
    shared-prefix pages plus the tail just written — under the absolute
    causal mask ``slot <= position``, which is exactly full-prompt prefill
    as long as nothing wrapped (prompts are admission-checked <= max_len).
    Returns (attn output (Bn,S,D), updated cache_layer)."""
    B, S, _ = x.shape
    P = cache_layer["k"].shape[1]
    max_pages = table.shape[1]
    T = max_pages * P
    q, k, v = gqa_project_qkv(p, cfg, x, positions)
    valid = jnp.arange(S)[None, :] < lengths[:, None]          # (Bn,S)
    slot = positions % T
    phys = jnp.take_along_axis(jnp.maximum(table, 0), slot // P, axis=1)
    phys = jnp.where(valid, phys, 0)                           # pad → trash
    off = slot % P
    new_k = cache_layer["k"].at[phys, off].set(k.astype(cache_layer["k"].dtype))
    new_v = cache_layer["v"].at[phys, off].set(v.astype(cache_layer["v"].dtype))
    # dense per-row view of the updated pool: (Bn, T, K, hd)
    from ..kernels.decode_attention.ref import gather_pages_ref
    kd = gather_pages_ref(new_k, table)
    vd = gather_pages_ref(new_v, table)
    K_h, hd = k.shape[2], k.shape[3]
    G = q.shape[2] // K_h
    qg = q.reshape(B, S, K_h, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, kd,
                        preferred_element_type=jnp.float32)
    scores = scores * attn_scale(cfg, hd)
    if cfg.attn_logit_softcap > 0.0:
        scores = cfg.attn_logit_softcap * jnp.tanh(
            scores / cfg.attn_logit_softcap)
    # absolute causal mask: ring slot t attendable by the query at
    # absolute position positions[b, s] iff t <= positions[b, s]
    mask = (jnp.arange(T)[None, None, None, None, :]
            <= positions[:, None, None, :, None])
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(vd.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, vd,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, S, K_h * G, hd).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": new_k, "v": new_v}


def decode_positions(pos, batch: int):
    """Normalise a decode position to per-row (B,) int32 (scalar broadcasts
    — the fixed-lockstep engine path and the slot pool share one code path)."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((batch,), pos, jnp.int32)
    return pos


def gqa_decode(p, cfg, x, cache_layer, pos):
    """One-token decode for one layer. x: (B,1,D); pos: int32 scalar or (B,)
    = number of tokens already in each row's context (per-row positions let
    a slot pool decode a ragged batch). Returns (out, new_cache_layer)."""
    B = x.shape[0]
    T = cache_layer["k"].shape[1]
    pos = decode_positions(pos, B)
    q, k, v = gqa_project_qkv(p, cfg, x, pos[:, None])  # q (B,1,H,hd); k,v (B,1,K,hd)
    new_cache = gqa_cache_write_decode(cache_layer, cfg, k, v, pos % T)
    if cfg.kv_cache_dtype == "int8":       # §Perf G5
        ck = _dequantize_kv(new_cache["k"], new_cache["k_scale"], k.dtype)
        cv = _dequantize_kv(new_cache["v"], new_cache["v_scale"], v.dtype)
    else:
        ck, cv = new_cache["k"], new_cache["v"]
    n_valid = jnp.minimum(pos + 1, T)                   # (B,)
    out = decode_ops.decode_attention(q, ck, cv, n_valid,
                                      softcap=cfg.attn_logit_softcap,
                                      scale=attn_scale(cfg, q.shape[-1]))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------

def cross_attn_init(key, cfg, dtype):
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": trunc_normal(ks[0], (D, H, hd), dtype=dtype),
        "wk": trunc_normal(ks[1], (D, H, hd), dtype=dtype),
        "wv": trunc_normal(ks[2], (D, H, hd), dtype=dtype),
        "wo": trunc_normal(ks[3], (H, hd, D), dtype=dtype),
    }


def cross_attn_kv(p, enc_out):
    """Precompute cross K/V from encoder output (a reusable request context)."""
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    return k, v


def cross_attn_apply(p, cfg, x, k, v):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = flash_ops.flash_attention(q, k, v, causal=False, window=0)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------

def mla_init(key, cfg, dtype):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": trunc_normal(ks[0], (D, m.q_lora_rank), dtype=dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "wq_b": trunc_normal(ks[1], (m.q_lora_rank, H, qk_hd), dtype=dtype),
        "wkv_a": trunc_normal(ks[2], (D, m.kv_lora_rank + m.qk_rope_head_dim),
                              dtype=dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "wk_b": trunc_normal(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                             dtype=dtype),
        "wv_b": trunc_normal(ks[4], (m.kv_lora_rank, H, m.v_head_dim),
                             dtype=dtype),
        "wo": trunc_normal(ks[5], (H, m.v_head_dim, D), dtype=dtype),
    }


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    q_lat = rmsnorm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"],
                    cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q_lat, p["wq_b"])
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, cfg, x, positions):
    """Compressed latent ckv (B,S,r) and shared roped key k_rope (B,S,rope)."""
    m = cfg.mla
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv = rmsnorm(kv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return ckv, k_rope


def mla_apply(p, cfg, x, *, positions=None):
    """Full-sequence MLA (train / prefill): decompress K,V then flash attention."""
    m = cfg.mla
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    ckv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["wv_b"])
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, cfg.n_heads, m.qk_rope_head_dim))],
        axis=-1)
    out = flash_ops.flash_attention(q, k, v, causal=True,
                                    window=cfg.attn_window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def mla_cache_init(cfg, batch: int, max_len: int, n_layers: int, dtype):
    m = cfg.mla
    T = gqa_cache_len(cfg, max_len)
    return {
        "ckv": jnp.zeros((n_layers, batch, T, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((n_layers, batch, T, m.qk_rope_head_dim), dtype),
    }


def mla_cache_write_prefill(cache_layer, cfg, ckv, k_rope, max_len: int):
    T = cache_layer["ckv"].shape[1]
    S = ckv.shape[1]
    if S > T:
        ckv, k_rope = ckv[:, S - T:], k_rope[:, S - T:]
    c1 = jax.lax.dynamic_update_slice_in_dim(cache_layer["ckv"], ckv, 0, axis=1)
    c2 = jax.lax.dynamic_update_slice_in_dim(cache_layer["k_rope"], k_rope, 0,
                                             axis=1)
    return {"ckv": c1, "k_rope": c2}


def mla_decode(p, cfg, x, cache_layer, pos):
    """Absorbed-form MLA decode: attention runs in the compressed latent space
    (this is the TPU-friendly 'weight absorption' trick from the DeepSeek
    papers — K/V are never decompressed per step).  ``pos`` is int32 scalar
    or (B,) per-row positions (slot-pool decode)."""
    m = cfg.mla
    B = x.shape[0]
    T = cache_layer["ckv"].shape[1]
    pos = decode_positions(pos, B)
    positions = pos[:, None]                             # (B,1)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)        # (B,1,H,·)
    ckv_new, k_rope_new = _mla_kv_latent(p, cfg, x, positions)
    rows = jnp.arange(B)
    slots = pos % T
    ckv = cache_layer["ckv"].at[rows, slots].set(ckv_new[:, 0])
    k_rope = cache_layer["k_rope"].at[rows, slots].set(k_rope_new[:, 0])
    # absorb wk_b into the query: q_lat (B,1,H,r)
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    scores = (jnp.einsum("bshr,btr->bhst", q_lat, ckv)
              + jnp.einsum("bshk,btk->bhst", q_rope, k_rope)).astype(jnp.float32)
    scores = scores * scale
    n_valid = jnp.minimum(pos + 1, T)                    # (B,)
    mask = jnp.arange(T)[None, None, None, :] < n_valid[:, None, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(ckv.dtype)
    out_lat = jnp.einsum("bhst,btr->bshr", probs, ckv)   # (B,1,H,r)
    out = jnp.einsum("bshr,rhk->bshk", out_lat, p["wv_b"])
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"ckv": ckv, "k_rope": k_rope}

"""Block zoo: one init/apply/prefill/decode quartet per block type.

A *block* is one layer of a stage; stages stack homogeneous blocks along a
leading layer axis and run them under ``lax.scan`` (models/model.py).
``apply`` is the cache-free path (training), ``prefill`` additionally emits
the block's decode cache, ``decode`` consumes/updates one layer's cache for
a single token.

Block types:
  dense      — GQA attention + SwiGLU            (granite, qwen3, olmo,
                                                  llama3, smollm2, llava)
  moe        — GQA attention + MoE FFN           (phi3.5-moe)
  dense_mla  — MLA attention + SwiGLU            (deepseek first_k_dense)
  moe_mla    — MLA attention + MoE FFN           (deepseek)
  hymba      — parallel GQA + SSM heads + SwiGLU (hymba)
  slstm/mlstm— xLSTM blocks (own up/down, no FFN)(xlstm)
  enc        — bidirectional attention + SwiGLU  (whisper encoder)
  dec        — causal self-attn + cross-attn + SwiGLU (whisper decoder)
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding import hint
from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from . import xlstm as xlstm_mod
from .layers import mlp_apply, mlp_init, rmsnorm, rmsnorm_init

ZERO = jnp.zeros((), jnp.float32)


def _norm_init(cfg, dtype):
    return None if cfg.nonparametric_norm else rmsnorm_init(cfg.d_model, dtype)


def _norm(x, w, cfg):
    return rmsnorm(x, w, cfg.norm_eps)


def _res_hint(x):
    return hint(x, "batch", "seq_act", "embed_act")


def _residual(x, y, cfg):
    """``x + y``, the branch ``y`` scaled by the residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return x + y


# ---------------------------------------------------------------------------
# dense / moe (GQA attention)
# ---------------------------------------------------------------------------

def dense_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg, dtype), "attn": attn.gqa_init(k1, cfg, dtype),
            "ln2": _norm_init(cfg, dtype),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}


def dense_apply(p, cfg, x, positions, extras):
    x = _residual(x, attn.gqa_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                                    positions=positions), cfg)
    x = _res_hint(x)
    x = _residual(x, mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg)), cfg)
    return _res_hint(x), ZERO


def dense_prefill(p, cfg, x, positions, extras, max_len):
    h = _norm(x, p["ln1"], cfg)
    q, k, v = attn.gqa_project_qkv(p["attn"], cfg, h, positions)
    from ..kernels.flash_attention import ops as flash_ops
    out = flash_ops.flash_attention(q, k, v, causal=True,
                                    window=cfg.attn_window,
                                    softcap=cfg.attn_logit_softcap,
                                    scale=attn.attn_scale(cfg, q.shape[-1]))
    x = _residual(x, jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"]), cfg)
    x = _res_hint(x)
    x = _residual(x, mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg)), cfg)
    T = attn.gqa_cache_len(cfg, max_len)
    B = x.shape[0]
    empty = attn.gqa_empty_cache_layer(cfg, B, max_len, k.dtype)
    cache = attn.gqa_cache_write_prefill(empty, cfg, k, v, max_len)
    return _res_hint(x), cache, ZERO


def _gqa_decode_routed(p, cfg, h, cache_layer, pos, extras):
    """Dense-ring or paged decode for one GQA layer, keyed on whether the
    caller's cache carries a page table (``extras["page_table"]``)."""
    table = extras.get("page_table")
    if table is not None:
        return attn.gqa_decode_paged(p, cfg, h, cache_layer, table, pos,
                                     write_mask=extras.get("step_mask"))
    return attn.gqa_decode(p, cfg, h, cache_layer, pos)


def dense_decode(p, cfg, x, cache_layer, pos, extras):
    y, cache_layer = _gqa_decode_routed(p["attn"], cfg,
                                        _norm(x, p["ln1"], cfg), cache_layer,
                                        pos, extras)
    x = _residual(x, y, cfg)
    x = _residual(x, mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg)), cfg)
    return x, cache_layer


def dense_prefill_paged(p, cfg, x, positions, cache_layer, table, lengths):
    """Tail prefill through the page table (shared prefix already paged)."""
    h = _norm(x, p["ln1"], cfg)
    y, cache_layer = attn.gqa_prefill_into_pages(p["attn"], cfg, h,
                                                 cache_layer, table,
                                                 positions, lengths)
    x = _residual(x, y, cfg)
    x = _res_hint(x)
    x = _residual(x, mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg)), cfg)
    return _res_hint(x), cache_layer


def dense_cache_init(cfg, batch, max_len, n_layers, dtype):
    return attn.gqa_cache_init(cfg, batch, max_len, n_layers, dtype)


def moe_init_fn(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg, dtype), "attn": attn.gqa_init(k1, cfg, dtype),
            "ln2": _norm_init(cfg, dtype), "moe": moe_mod.moe_init(k2, cfg, dtype)}


def moe_apply_fn(p, cfg, x, positions, extras):
    x = x + attn.gqa_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                           positions=positions)
    x = _res_hint(x)
    y, aux = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return _res_hint(x + y), aux


def moe_prefill(p, cfg, x, positions, extras, max_len):
    h = _norm(x, p["ln1"], cfg)
    q, k, v = attn.gqa_project_qkv(p["attn"], cfg, h, positions)
    from ..kernels.flash_attention import ops as flash_ops
    out = flash_ops.flash_attention(q, k, v, causal=True,
                                    window=cfg.attn_window)
    x = x + jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
    y, aux = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    x = x + y
    T = attn.gqa_cache_len(cfg, max_len)
    B = x.shape[0]
    empty = attn.gqa_empty_cache_layer(cfg, B, max_len, k.dtype)
    cache = attn.gqa_cache_write_prefill(empty, cfg, k, v, max_len)
    return _res_hint(x), cache, aux


def moe_decode(p, cfg, x, cache_layer, pos, extras):
    y, cache_layer = _gqa_decode_routed(p["attn"], cfg,
                                        _norm(x, p["ln1"], cfg), cache_layer,
                                        pos, extras)
    x = x + y
    y, _ = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return x + y, cache_layer


def moe_prefill_paged(p, cfg, x, positions, cache_layer, table, lengths):
    h = _norm(x, p["ln1"], cfg)
    y, cache_layer = attn.gqa_prefill_into_pages(p["attn"], cfg, h,
                                                 cache_layer, table,
                                                 positions, lengths)
    x = x + y
    y, _ = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return _res_hint(x + y), cache_layer


# ---------------------------------------------------------------------------
# MLA variants (deepseek)
# ---------------------------------------------------------------------------

def dense_mla_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg, dtype), "attn": attn.mla_init(k1, cfg, dtype),
            "ln2": _norm_init(cfg, dtype),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}


def dense_mla_apply(p, cfg, x, positions, extras):
    x = x + attn.mla_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                           positions=positions)
    x = _res_hint(x)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return _res_hint(x), ZERO


def _mla_prefill_cache(p, cfg, h, positions, max_len):
    ckv, k_rope = attn._mla_kv_latent(p, cfg, h, positions)
    T = attn.gqa_cache_len(cfg, max_len)
    B = h.shape[0]
    m = cfg.mla
    empty = {"ckv": jnp.zeros((B, T, m.kv_lora_rank), ckv.dtype),
             "k_rope": jnp.zeros((B, T, m.qk_rope_head_dim), k_rope.dtype)}
    return attn.mla_cache_write_prefill(empty, cfg, ckv, k_rope, max_len)


def dense_mla_prefill(p, cfg, x, positions, extras, max_len):
    h = _norm(x, p["ln1"], cfg)
    cache = _mla_prefill_cache(p["attn"], cfg, h, positions, max_len)
    x = x + attn.mla_apply(p["attn"], cfg, h, positions=positions)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return _res_hint(x), cache, ZERO


def dense_mla_decode(p, cfg, x, cache_layer, pos, extras):
    y, cache_layer = attn.mla_decode(p["attn"], cfg,
                                     _norm(x, p["ln1"], cfg), cache_layer, pos)
    x = x + y
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return x, cache_layer


def moe_mla_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {"ln1": _norm_init(cfg, dtype), "attn": attn.mla_init(k1, cfg, dtype),
            "ln2": _norm_init(cfg, dtype), "moe": moe_mod.moe_init(k2, cfg, dtype)}


def moe_mla_apply(p, cfg, x, positions, extras):
    x = x + attn.mla_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                           positions=positions)
    x = _res_hint(x)
    y, aux = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return _res_hint(x + y), aux


def moe_mla_prefill(p, cfg, x, positions, extras, max_len):
    h = _norm(x, p["ln1"], cfg)
    cache = _mla_prefill_cache(p["attn"], cfg, h, positions, max_len)
    x = x + attn.mla_apply(p["attn"], cfg, h, positions=positions)
    y, aux = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return _res_hint(x + y), cache, aux


def moe_mla_decode(p, cfg, x, cache_layer, pos, extras):
    y, cache_layer = attn.mla_decode(p["attn"], cfg,
                                     _norm(x, p["ln1"], cfg), cache_layer, pos)
    x = x + y
    y, _ = moe_mod.moe_apply(p["moe"], cfg, _norm(x, p["ln2"], cfg))
    return x + y, cache_layer


def mla_cache_init(cfg, batch, max_len, n_layers, dtype):
    return attn.mla_cache_init(cfg, batch, max_len, n_layers, dtype)


# ---------------------------------------------------------------------------
# hymba (parallel attention + SSM heads)
# ---------------------------------------------------------------------------

def hymba_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": _norm_init(cfg, dtype),
        "attn": attn.gqa_init(k1, cfg, dtype),
        "ssm": ssm_mod.ssm_init(k2, cfg, dtype),
        "attn_norm": rmsnorm_init(cfg.d_model, dtype),
        "ssm_norm": rmsnorm_init(cfg.d_model, dtype),
        "ln2": _norm_init(cfg, dtype),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def _hymba_fuse(p, cfg, a, s):
    return 0.5 * (rmsnorm(a, p["attn_norm"], cfg.norm_eps)
                  + rmsnorm(s, p["ssm_norm"], cfg.norm_eps))


def hymba_apply(p, cfg, x, positions, extras):
    h = _norm(x, p["ln1"], cfg)
    a = attn.gqa_apply(p["attn"], cfg, h, positions=positions)
    s = ssm_mod.ssm_apply(p["ssm"], cfg, h)
    x = x + _hymba_fuse(p, cfg, a, s)
    x = _res_hint(x)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return _res_hint(x), ZERO


def hymba_prefill(p, cfg, x, positions, extras, max_len):
    h = _norm(x, p["ln1"], cfg)
    q, k, v = attn.gqa_project_qkv(p["attn"], cfg, h, positions)
    from ..kernels.flash_attention import ops as flash_ops
    out = flash_ops.flash_attention(q, k, v, causal=True,
                                    window=cfg.attn_window)
    a = jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
    s, ssm_cache = ssm_mod.ssm_prefill(p["ssm"], cfg, h)
    x = x + _hymba_fuse(p, cfg, a, s)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    T = attn.gqa_cache_len(cfg, max_len)
    B = x.shape[0]
    empty = attn.gqa_empty_cache_layer(cfg, B, max_len, k.dtype)
    kv = attn.gqa_cache_write_prefill(empty, cfg, k, v, max_len)
    cache = {**kv, **ssm_cache}
    return _res_hint(x), cache, ZERO


def hymba_decode(p, cfg, x, cache_layer, pos, extras):
    h = _norm(x, p["ln1"], cfg)
    kv_cache = {"k": cache_layer["k"], "v": cache_layer["v"]}
    a, kv_cache = attn.gqa_decode(p["attn"], cfg, h, kv_cache, pos)
    ssm_cache = {"conv": cache_layer["conv"], "h": cache_layer["h"]}
    s, ssm_cache = ssm_mod.ssm_decode(p["ssm"], cfg, h, ssm_cache)
    x = x + _hymba_fuse(p, cfg, a, s)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return x, {**kv_cache, **ssm_cache}


def hymba_cache_init(cfg, batch, max_len, n_layers, dtype):
    kv = attn.gqa_cache_init(cfg, batch, max_len, n_layers, dtype)
    s = ssm_mod.ssm_cache_init(cfg, batch, n_layers, dtype)
    return {**kv, **s}


# ---------------------------------------------------------------------------
# xLSTM blocks
# ---------------------------------------------------------------------------

def slstm_block_init(key, cfg, dtype):
    return {"ln": _norm_init(cfg, dtype),
            "cell": xlstm_mod.slstm_init(key, cfg, dtype)}


def slstm_block_apply(p, cfg, x, positions, extras):
    y = xlstm_mod._batch_local(xlstm_mod.slstm_apply, p["cell"], cfg,
                               _norm(x, p["ln"], cfg), False)
    return _res_hint(x + y), ZERO


def slstm_block_prefill(p, cfg, x, positions, extras, max_len):
    y, st = xlstm_mod._batch_local(xlstm_mod.slstm_apply, p["cell"], cfg,
                                   _norm(x, p["ln"], cfg), True)
    return _res_hint(x + y), st, ZERO


def slstm_block_decode(p, cfg, x, cache_layer, pos, extras):
    y, st = xlstm_mod.slstm_decode(p["cell"], cfg, _norm(x, p["ln"], cfg),
                                   cache_layer)
    return x + y, st


def mlstm_block_init(key, cfg, dtype):
    return {"ln": _norm_init(cfg, dtype),
            "cell": xlstm_mod.mlstm_init(key, cfg, dtype)}


def mlstm_block_apply(p, cfg, x, positions, extras):
    y = xlstm_mod._batch_local(xlstm_mod.mlstm_apply, p["cell"], cfg,
                               _norm(x, p["ln"], cfg), False)
    return _res_hint(x + y), ZERO


def mlstm_block_prefill(p, cfg, x, positions, extras, max_len):
    y, st = xlstm_mod._batch_local(xlstm_mod.mlstm_apply, p["cell"], cfg,
                                   _norm(x, p["ln"], cfg), True)
    return _res_hint(x + y), st, ZERO


def mlstm_block_decode(p, cfg, x, cache_layer, pos, extras):
    y, st = xlstm_mod.mlstm_decode(p["cell"], cfg, _norm(x, p["ln"], cfg),
                                   cache_layer)
    return x + y, st


# ---------------------------------------------------------------------------
# whisper encoder / decoder
# ---------------------------------------------------------------------------

def enc_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = attn.gqa_init(k1, cfg, dtype)
    return {"ln1": rmsnorm_init(cfg.d_model, dtype), "attn": p,
            "ln2": rmsnorm_init(cfg.d_model, dtype),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)}


def enc_apply(p, cfg, x, positions, extras):
    x = x + attn.gqa_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                           positions=positions, causal=False)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return _res_hint(x), ZERO


def dec_init(key, cfg, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"ln1": rmsnorm_init(cfg.d_model, dtype),
            "attn": attn.gqa_init(k1, cfg, dtype),
            "ln_x": rmsnorm_init(cfg.d_model, dtype),
            "xattn": attn.cross_attn_init(k2, cfg, dtype),
            "ln2": rmsnorm_init(cfg.d_model, dtype),
            "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype)}


def dec_apply(p, cfg, x, positions, extras):
    enc_out = extras["enc_out"]
    x = x + attn.gqa_apply(p["attn"], cfg, _norm(x, p["ln1"], cfg),
                           positions=positions)
    ck, cv = attn.cross_attn_kv(p["xattn"], enc_out)
    x = x + attn.cross_attn_apply(p["xattn"], cfg, _norm(x, p["ln_x"], cfg),
                                  ck, cv)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return _res_hint(x), ZERO


def dec_prefill(p, cfg, x, positions, extras, max_len):
    enc_out = extras["enc_out"]
    h = _norm(x, p["ln1"], cfg)
    q, k, v = attn.gqa_project_qkv(p["attn"], cfg, h, positions)
    from ..kernels.flash_attention import ops as flash_ops
    out = flash_ops.flash_attention(q, k, v, causal=True, window=0)
    x = x + jnp.einsum("bshk,hkd->bsd", out, p["attn"]["wo"])
    ck, cv = attn.cross_attn_kv(p["xattn"], enc_out)
    x = x + attn.cross_attn_apply(p["xattn"], cfg, _norm(x, p["ln_x"], cfg),
                                  ck, cv)
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    T = attn.gqa_cache_len(cfg, max_len)
    B = x.shape[0]
    empty = attn.gqa_empty_cache_layer(cfg, B, max_len, k.dtype)
    kv = attn.gqa_cache_write_prefill(empty, cfg, k, v, max_len)
    return _res_hint(x), {**kv, "ck": ck, "cv": cv}, ZERO


def dec_decode(p, cfg, x, cache_layer, pos, extras):
    kv = {"k": cache_layer["k"], "v": cache_layer["v"]}
    y, kv = attn.gqa_decode(p["attn"], cfg, _norm(x, p["ln1"], cfg), kv, pos)
    x = x + y
    x = x + attn.cross_attn_apply(p["xattn"], cfg, _norm(x, p["ln_x"], cfg),
                                  cache_layer["ck"], cache_layer["cv"])
    x = x + mlp_apply(p["mlp"], _norm(x, p["ln2"], cfg))
    return x, {**kv, "ck": cache_layer["ck"], "cv": cache_layer["cv"]}


def dec_cache_init(cfg, batch, max_len, n_layers, dtype):
    kv = attn.gqa_cache_init(cfg, batch, max_len, n_layers, dtype)
    H, hd = cfg.n_heads, cfg.resolved_head_dim
    F = cfg.n_audio_frames
    kv["ck"] = jnp.zeros((n_layers, batch, F, H, hd), dtype)
    kv["cv"] = jnp.zeros((n_layers, batch, F, H, hd), dtype)
    return kv


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

BLOCKS: Dict[str, Dict[str, Any]] = {
    "dense": dict(init=dense_init, apply=dense_apply, prefill=dense_prefill,
                  decode=dense_decode, cache_init=dense_cache_init,
                  prefill_paged=dense_prefill_paged),
    "moe": dict(init=moe_init_fn, apply=moe_apply_fn, prefill=moe_prefill,
                decode=moe_decode, cache_init=dense_cache_init,
                prefill_paged=moe_prefill_paged),
    "dense_mla": dict(init=dense_mla_init, apply=dense_mla_apply,
                      prefill=dense_mla_prefill, decode=dense_mla_decode,
                      cache_init=mla_cache_init),
    "moe_mla": dict(init=moe_mla_init, apply=moe_mla_apply,
                    prefill=moe_mla_prefill, decode=moe_mla_decode,
                    cache_init=mla_cache_init),
    "hymba": dict(init=hymba_init, apply=hymba_apply, prefill=hymba_prefill,
                  decode=hymba_decode, cache_init=hymba_cache_init),
    "slstm": dict(init=slstm_block_init, apply=slstm_block_apply,
                  prefill=slstm_block_prefill, decode=slstm_block_decode,
                  cache_init=lambda cfg, b, m, n, dt:
                      xlstm_mod.slstm_state_init(cfg, b, n)),
    "mlstm": dict(init=mlstm_block_init, apply=mlstm_block_apply,
                  prefill=mlstm_block_prefill, decode=mlstm_block_decode,
                  cache_init=lambda cfg, b, m, n, dt:
                      xlstm_mod.mlstm_state_init(cfg, b, n)),
    "enc": dict(init=enc_init, apply=enc_apply, prefill=None, decode=None,
                cache_init=None),
    "dec": dict(init=dec_init, apply=dec_apply, prefill=dec_prefill,
                decode=dec_decode, cache_init=dec_cache_init),
}

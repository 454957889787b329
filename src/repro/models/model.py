"""Model assembly: stages of scanned homogeneous blocks + LM heads.

Public API (all pure functions of (cfg, params, ...)):

  init_params(cfg, key)                  -> params pytree
  forward(cfg, params, batch)            -> full logits (small models/tests)
  loss_fn(cfg, params, batch)            -> (loss, metrics)   [chunked CE]
  cache_init(cfg, batch, max_len)        -> decode cache pytree
  prefill(cfg, params, batch, max_len)   -> (last-token logits, cache)
  decode_step(cfg, params, cache, tok)   -> (logits (B,1,V), cache)

Layer stacks are grouped into consecutive homogeneous *stages* (run-length
encoding of the block-type sequence) and each stage runs under
``jax.lax.scan`` over stacked params — HLO size stays O(#stages), which is
what makes the 126-layer llama3-405b dry-run compile tractable.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding import cotangent_dtype_pin, grad_hint, hint
from .blocks import BLOCKS
from .layers import (embed_init, rmsnorm, rmsnorm_init, sinusoidal_positions,
                     trunc_normal)

VISION_EMBED_DIM = 1024          # stub ViT tower output width (llava)


# ---------------------------------------------------------------------------
# Stage layout
# ---------------------------------------------------------------------------

def layer_types(cfg) -> List[str]:
    if cfg.block_pattern:
        return list(cfg.block_pattern)
    if cfg.family == "audio":
        return ["dec"] * cfg.n_layers
    if cfg.mla is not None and cfg.moe is not None:
        return (["dense_mla"] * cfg.first_k_dense
                + ["moe_mla"] * (cfg.n_layers - cfg.first_k_dense))
    if cfg.moe is not None:
        return ["moe"] * cfg.n_layers
    if cfg.hybrid_parallel_heads:
        return ["hymba"] * cfg.n_layers
    return ["dense"] * cfg.n_layers


def stages_for(cfg) -> List[Tuple[str, int]]:
    """Run-length encode the layer-type sequence into scanned stages."""
    out: List[Tuple[str, int]] = []
    for t in layer_types(cfg):
        if out and out[-1][0] == t:
            out[-1] = (t, out[-1][1] + 1)
        else:
            out.append((t, 1))
    return out


def _stack_layers(key, cfg, btype: str, n: int, dtype):
    init = BLOCKS[btype]["init"]
    keys = jax.random.split(key, n)
    per_layer = [init(k, cfg, dtype) for k in keys]
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_layer)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_params(cfg, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        # the table is drawn so that its scaled rows, embed[ids] * m, start
        # at the initialiser's scale (0.02) whatever the multiplier m
        "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dtype,
                            scale=0.02 / cfg.embedding_multiplier),
        "final_norm": (None if cfg.nonparametric_norm
                       else rmsnorm_init(cfg.d_model, dtype)),
        "stages": [
            _stack_layers(jax.random.fold_in(keys[1], i), cfg, btype, n, dtype)
            for i, (btype, n) in enumerate(stages_for(cfg))
        ],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = trunc_normal(keys[2],
                                         (cfg.d_model, cfg.vocab_size),
                                         dtype=dtype)
    if cfg.family == "vlm":
        params["proj_vision"] = {
            "w1": trunc_normal(keys[3], (VISION_EMBED_DIM, cfg.d_model),
                               dtype=dtype),
            "w2": trunc_normal(keys[4], (cfg.d_model, cfg.d_model),
                               dtype=dtype),
        }
    if cfg.is_encdec:
        params["enc"] = {
            "stages": [_stack_layers(keys[5], cfg, "enc", cfg.encoder_layers,
                                     dtype)],
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
    if cfg.mtp_depth:
        mtp_key = keys[6]
        btype = "moe_mla" if (cfg.mla and cfg.moe) else "dense"
        params["mtp"] = {
            "block": _stack_layers(mtp_key, cfg, btype, 1, dtype),
            "proj": trunc_normal(jax.random.fold_in(mtp_key, 1),
                                 (2 * cfg.d_model, cfg.d_model), dtype=dtype),
            "norm_h": rmsnorm_init(cfg.d_model, dtype),
            "norm_e": rmsnorm_init(cfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# Embedding of inputs
# ---------------------------------------------------------------------------

def _embed_tokens(cfg, params, tokens, base_pos=None):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.rope_theta <= 0:      # sinusoidal absolute positions (whisper)
        S = tokens.shape[1]
        table = jnp.asarray(sinusoidal_positions(
            max(4096, S + 1), cfg.d_model), dtype=x.dtype)
        if base_pos is None:
            x = x + table[None, :S]
        else:
            base = jnp.asarray(base_pos, jnp.int32)
            if base.ndim == 0:
                x = x + jax.lax.dynamic_slice_in_dim(table, base, S)[None]
            else:            # per-row decode positions: (B,) gather
                x = x + table[base[:, None] + jnp.arange(S)]
    return x


def _proj_vision(params, vision_embeds):
    h = jnp.einsum("bpe,ed->bpd", vision_embeds, params["proj_vision"]["w1"])
    h = jax.nn.gelu(h)
    return jnp.einsum("bpd,de->bpe", h, params["proj_vision"]["w2"])


def _encode_audio(cfg, params, audio_embeds):
    F = audio_embeds.shape[1]
    table = jnp.asarray(sinusoidal_positions(F, cfg.d_model),
                        dtype=audio_embeds.dtype)
    x = audio_embeds + table[None]
    positions = jnp.arange(F, dtype=jnp.int32)
    for stacked in params["enc"]["stages"]:
        def body(carry, layer_p):
            y, _ = BLOCKS["enc"]["apply"](layer_p, cfg, carry, positions, {})
            return y, None
        x, _ = jax.lax.scan(body, x, stacked)
    return rmsnorm(x, params["enc"]["final_norm"], cfg.norm_eps)


def embed_batch(cfg, params, batch):
    """Returns (x (B,S,D), positions (S,), extras, n_prefix).

    n_prefix = number of leading positions with no LM labels (vision tiles)."""
    extras: Dict[str, Any] = {}
    n_prefix = 0
    tokens = batch["tokens"]
    x = _embed_tokens(cfg, params, tokens)
    if cfg.family == "vlm":
        v = _proj_vision(params, batch["vision_embeds"].astype(x.dtype))
        x = jnp.concatenate([v, x], axis=1)
        n_prefix = v.shape[1]
    if cfg.is_encdec:
        extras["enc_out"] = _encode_audio(
            cfg, params, batch["audio_embeds"])
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    x = hint(x, "batch", "seq_act", "embed_act")
    return x, positions, extras, n_prefix


# ---------------------------------------------------------------------------
# Stage execution
# ---------------------------------------------------------------------------

def _run_stages_apply(cfg, params, x, positions, extras):
    aux_total = jnp.zeros((), jnp.float32)
    for (btype, _n), stacked in zip(stages_for(cfg), params["stages"]):
        apply = BLOCKS[btype]["apply"]

        def body(carry, layer_p, _apply=apply):
            layer_p = grad_hint(layer_p)     # keep dW sharded in the bwd
            carry = cotangent_dtype_pin(carry, carry.dtype)  # bf16 dx
            y, aux = _apply(layer_p, cfg, carry, positions, extras)
            return y, aux

        if cfg.parallel.remat == "block":
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, stacked)
        aux_total = aux_total + jnp.sum(auxs)
    return x, aux_total


def _run_stages_prefill(cfg, params, x, positions, extras, max_len):
    caches = []
    for (btype, _n), stacked in zip(stages_for(cfg), params["stages"]):
        prefill = BLOCKS[btype]["prefill"]

        def body(carry, layer_p, _prefill=prefill):
            y, cache_l, aux = _prefill(layer_p, cfg, carry, positions, extras,
                                       max_len)
            return y, (cache_l, aux)

        x, (cache_i, _auxs) = jax.lax.scan(body, x, stacked)
        caches.append(cache_i)
    return x, caches


def _run_stages_decode(cfg, params, x, caches, pos, extras):
    new_caches = []
    for (btype, _n), stacked, cache_i in zip(stages_for(cfg),
                                             params["stages"], caches):
        decode = BLOCKS[btype]["decode"]

        def body(carry, xs, _decode=decode):
            layer_p, cache_l = xs
            y, new_cache_l = _decode(layer_p, cfg, carry, cache_l, pos, extras)
            return y, new_cache_l

        x, new_cache_i = jax.lax.scan(body, x, (stacked, cache_i))
        new_caches.append(new_cache_i)
    return x, new_caches


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def _unembed(cfg, params, h):
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", h, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", h, params["lm_head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def forward(cfg, params, batch):
    """Full-sequence logits — for tests/small models (materialises B,S,V)."""
    x, positions, extras, _ = embed_batch(cfg, params, batch)
    h, _aux = _run_stages_apply(cfg, params, x, positions, extras)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return _unembed(cfg, params, h)


def _chunked_ce(cfg, params, h, targets, mask, chunk: int = 1024):
    """Cross-entropy without materialising (B,S,V): scan over seq chunks.

    h: (B,S,D); targets, mask: (B,S). Returns (sum_nll, sum_mask)."""
    B, S, D = h.shape
    pad = (-S) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (S + pad) // chunk
    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)
    mc = mask.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        h_i, t_i, m_i = xs
        logits = _unembed(cfg, params, h_i).astype(jnp.float32)
        logits = hint(logits, "batch", None, "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, t_i[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * m_i
        return (acc[0] + jnp.sum(nll), acc[1] + jnp.sum(m_i)), None

    (nll_sum, m_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hc, tc, mc))
    return nll_sum, m_sum


def loss_fn(cfg, params, batch):
    """Next-token LM loss (+ MoE aux + optional MTP). batch['tokens'] (B,S)."""
    tokens = batch["tokens"]
    x, positions, extras, n_prefix = embed_batch(cfg, params, batch)
    h, aux = _run_stages_apply(cfg, params, x, positions, extras)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    h_text = h[:, n_prefix:]                       # positions with labels
    B, S = tokens.shape
    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    mask = jnp.pad(jnp.ones((B, S - 1), jnp.float32), ((0, 0), (0, 1)))
    if "loss_mask" in batch:
        mask = mask * batch["loss_mask"].astype(jnp.float32)
    nll_sum, m_sum = _chunked_ce(cfg, params, h_text, targets, mask)
    loss = nll_sum / jnp.maximum(m_sum, 1.0)
    metrics = {"ce": loss, "aux": aux, "tokens": m_sum}
    if cfg.mtp_depth and "mtp" in params:
        mtp = params["mtp"]
        emb_next = jnp.take(params["embed"], targets, axis=0)
        h_in = jnp.concatenate(
            [rmsnorm(h_text, mtp["norm_h"], cfg.norm_eps),
             rmsnorm(emb_next, mtp["norm_e"], cfg.norm_eps)], axis=-1)
        h_in = jnp.einsum("bsd,dk->bsk", h_in, mtp["proj"])
        btype = "moe_mla" if (cfg.mla and cfg.moe) else "dense"
        layer_p = jax.tree_util.tree_map(lambda a: a[0], mtp["block"])
        h_mtp, _ = BLOCKS[btype]["apply"](layer_p, cfg, h_in, positions[:S],
                                          extras)
        # at position i we now predict t_{i+2}
        t2 = jnp.pad(tokens[:, 2:], ((0, 0), (0, 2)))
        m2 = jnp.pad(jnp.ones((B, S - 2), jnp.float32), ((0, 0), (0, 2)))
        nll2, ms2 = _chunked_ce(cfg, params, h_mtp, t2, m2)
        mtp_loss = nll2 / jnp.maximum(ms2, 1.0)
        metrics["mtp"] = mtp_loss
        loss = loss + 0.3 * mtp_loss
    loss = loss + aux
    metrics["loss"] = loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def cache_init(cfg, batch: int, max_len: int):
    """Decode cache with PER-ROW positions: ``cache["pos"]`` is (B,) int32,
    so each row decodes at its own sequence length (slot-pool serving); the
    lock-step engine path simply keeps all rows equal."""
    dtype = jnp.dtype(cfg.dtype)
    caches = []
    for btype, n in stages_for(cfg):
        ci = BLOCKS[btype]["cache_init"]
        caches.append(ci(cfg, batch, max_len, n, dtype))
    return {"stages": caches, "pos": jnp.zeros((batch,), jnp.int32)}


def supports_paging(cfg) -> bool:
    """Whether this model family can run the PAGED decode cache.

    Paging covers the GQA ring-KV block types.  Recurrent state (SSM/
    xLSTM/hymba), MLA latents, cross-attention K/V, int8-quantised caches
    and sliding-window rings have only the contiguous ``cache_init``
    layout, which the lock-step engine uses; the streaming slot pool
    refuses them."""
    return (all(t in ("dense", "moe") for t in layer_types(cfg))
            and cfg.kv_cache_dtype != "int8"
            and not cfg.attn_window
            and cfg.family != "vlm"
            and not cfg.is_encdec)


def paged_cache_init(cfg, batch: int, n_pages: int, page_size: int,
                     max_pages: int):
    """Paged decode cache: per-stage physical page pools + the page table.

    ``cache["stages"]`` leaves are (L, n_pages, P, K, hd) page POOLS shared
    by every row; ``cache["table"]`` (B, max_pages) int32 maps each row's
    logical pages to physical ones (0 = unmapped → the reserved trash
    page); ``cache["pos"]`` stays per-row.  The logical ring length is
    ``max_pages * page_size``."""
    assert supports_paging(cfg), "model family does not support paged KV"
    from .attention import gqa_paged_cache_init
    dtype = jnp.dtype(cfg.dtype)
    caches = [gqa_paged_cache_init(cfg, n_pages, page_size, n, dtype)
              for _btype, n in stages_for(cfg)]
    return {"stages": caches, "pos": jnp.zeros((batch,), jnp.int32),
            "table": jnp.zeros((batch, max_pages), jnp.int32)}


def prefill(cfg, params, batch, max_len: int):
    """Run the prompt, build the decode cache. Returns (last logits, cache)."""
    x, positions, extras, _n_prefix = embed_batch(cfg, params, batch)
    x, caches = _run_stages_prefill(cfg, params, x, positions, extras, max_len)
    h = rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    B, S = x.shape[0], x.shape[1]
    return logits, {"stages": caches, "pos": jnp.full((B,), S, jnp.int32)}


def decode_step(cfg, params, cache, tokens, step_mask=None):
    """One token for the whole batch. tokens: (B,1). Returns (logits, cache).

    ``cache["pos"]`` is per-row, so rows may sit at different lengths: each
    embeds/RoPEs at its own position, ring-writes K/V at its own slot, and
    masks attention at its own valid length.

    ``step_mask`` (B,) bool marks the rows actually decoding; unmasked rows
    (free / not-yet-admitted slots of a slot pool) keep their position, and
    the junk K/V the lock-step write leaves at an unmasked row's current
    slot is overwritten by that row's next REAL step before it is ever
    attended (the write-then-attend order makes idle rows self-healing for
    ring-cache attention; SSM/xLSTM state rows are only exact when every
    occupied slot steps together)."""
    pos = cache["pos"]
    if cfg.rope_theta <= 0:
        x = _embed_tokens(cfg, params, tokens, base_pos=pos)
    else:
        x = _embed_tokens(cfg, params, tokens)
    extras: Dict[str, Any] = {}
    if "table" in cache:            # paged cache: route writes/attention
        extras["page_table"] = cache["table"]
        if step_mask is not None:   # idle rows' junk writes → trash page
            extras["step_mask"] = jnp.asarray(step_mask)
    x = hint(x, "batch", None, "embed_act")
    x, new_caches = _run_stages_decode(cfg, params, x, cache["stages"], pos,
                                       extras)
    h = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    new_pos = pos + 1 if step_mask is None else \
        jnp.where(jnp.asarray(step_mask), pos + 1, pos)
    # preserve any additional cache entries (the page table) verbatim
    return logits, {**cache, "stages": new_caches, "pos": new_pos}


def prefill_into_pages(cfg, params, batch, cache, slots, base, lengths):
    """Tail-only prefill for newly admitted rows of a PAGED slot pool.

    The shared prompt prefix — ``base`` (Bn,) tokens per row, page-aligned —
    is ALREADY resident in refcounted pages mapped by each row's page
    table, so only the unshared tail ``batch["tokens"]`` (Bn, S_tail,
    right-padded to a bucketed S) runs through the model: admission FLOPs
    and fresh KV bytes are flat in the shared-prefix length.  Tail queries
    attend over the row's whole mapped ring (shared pages + the tail being
    written) under the absolute causal mask, which equals full-prompt
    prefill exactly — shared pages hold the same post-RoPE K at the same
    absolute positions any private prefill would have written.

    ``slots`` (Bn,) are the rows' table indices; ``lengths`` (Bn,) the true
    tail token counts (padding positions write to the trash page).  The
    caller must have mapped private pages covering ``[base, base+length)``
    in ``cache["table"]`` before calling.

    Several rows may share one slot, each holding a consecutive chunk of
    one prompt: every layer writes all rows' K/V before any row attends,
    so a chunk reads the chunks before it from the same launch.  The
    slot's position becomes the furthest ``base + length`` among its
    rows, written alike by each of them (a scatter with duplicate
    indices keeps an unspecified one of its values).  Returns
    (next-token logits (Bn,1,V) gathered at each row's last real tail
    position, updated cache)."""
    tokens = batch["tokens"]
    Bn, S = tokens.shape
    base = jnp.asarray(base, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    x = _embed_tokens(cfg, params, tokens, base_pos=base)
    positions = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    table_rows = jnp.take(cache["table"], slots, axis=0)   # (Bn, max_pages)
    x = hint(x, "batch", "seq_act", "embed_act")
    new_stages = []
    for (btype, _n), stacked, cache_i in zip(stages_for(cfg),
                                             params["stages"],
                                             cache["stages"]):
        paged_prefill = BLOCKS[btype]["prefill_paged"]

        def body(carry, xs, _pp=paged_prefill):
            layer_p, cache_l = xs
            y, new_cache_l = _pp(layer_p, cfg, carry, positions, cache_l,
                                 table_rows, lengths)
            return y, new_cache_l

        x, new_cache_i = jax.lax.scan(body, x, (stacked, cache_i))
        new_stages.append(new_cache_i)
    D = x.shape[-1]
    last = lengths - 1
    h = jnp.take_along_axis(
        x, jnp.broadcast_to(last[:, None, None], (Bn, 1, D)), axis=1)
    h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    end = jnp.zeros_like(cache["pos"]).at[slots].max(base + lengths)
    new_pos = cache["pos"].at[slots].set(end[slots])
    return logits, {**cache, "stages": new_stages, "pos": new_pos}


def count_params(params) -> int:
    return int(sum(np.prod(x.shape)
                   for x in jax.tree_util.tree_leaves(params)))

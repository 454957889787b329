"""Scan-depth-corrected roofline terms from the compiled dry-run.

XLA's ``cost_analysis()`` on the partitioned module reports PER-DEVICE
numbers and counts each ``lax.scan`` body ONCE regardless of trip count
(verified empirically — see EXPERIMENTS.md §Roofline methodology).  Since
the models scan over layers, the raw numbers undercount by ~n_layers.

Correction: lower each stage's body separately (same mesh, same logical-
axis shardings), take its per-device flops / bytes / collective bytes, and
add ``(trip_count - 1) ×`` body for every scanned stage.  Train bodies are
lowered as ``grad(body)`` (fwd+bwd+remat — matching what the full step's
forward and backward scans contain); decode bodies take a per-layer cache
slice.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import InputShape, ModelConfig
from ..models import model as M
from ..models.blocks import BLOCKS
from ..models.model import VISION_EMBED_DIM, stages_for
from ..sharding import ShardingCtx, cache_specs, param_specs


def _ns(ctx: ShardingCtx, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def _strip_lead(spec: P) -> P:
    return P(*tuple(spec)[1:])


def _body_metrics(fn, args, in_sh, parse_collectives) -> Dict[str, float]:
    lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(sum(coll.values())),
    }


def stage_body_metrics(cfg: ModelConfig, shape: InputShape,
                       ctx: ShardingCtx, btype: str,
                       parse_collectives) -> Dict[str, float]:
    """Per-device metrics of ONE scanned iteration of stage ``btype``."""
    dtype = jnp.dtype(cfg.dtype)
    layer_p = jax.eval_shape(
        lambda k: BLOCKS[btype]["init"](k, cfg, dtype), jax.random.PRNGKey(0))
    p_sh = _ns(ctx, param_specs(layer_p, ctx))
    B, S = shape.global_batch, shape.seq_len
    D = cfg.d_model
    extras_spec: Dict[str, Any] = {}
    extras_sh: Dict[str, Any] = {}
    if btype in ("dec",):                      # whisper decoder cross-attn
        extras_spec["enc_out"] = jax.ShapeDtypeStruct(
            (B, cfg.n_audio_frames, D), dtype)
        extras_sh["enc_out"] = ctx.sharding(("batch", None, None),
                                            extras_spec["enc_out"].shape)

    if shape.kind == "decode":
        x = jax.ShapeDtypeStruct((B, 1, D), dtype)
        x_sh = ctx.sharding(("batch", None, "embed_act"), x.shape)
        cache1 = jax.eval_shape(
            lambda: BLOCKS[btype]["cache_init"](cfg, B, shape.seq_len, 1,
                                                dtype))
        cache_l = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), cache1)
        c_sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(ctx.mesh, _strip_lead(s)),
            cache_specs(cache1, ctx), is_leaf=lambda s: isinstance(s, P))
        pos = jax.ShapeDtypeStruct((), jnp.int32)

        def fn(p, xx, cl, pp, ex):
            return BLOCKS[btype]["decode"](p, cfg, xx, cl, pp, ex)

        return _body_metrics(fn, (layer_p, x, cache_l, pos, extras_spec),
                             (p_sh, x_sh, c_sh,
                              NamedSharding(ctx.mesh, P()), extras_sh),
                             parse_collectives)

    S_eff = S + (cfg.n_vision_patches if cfg.family == "vlm" else 0)
    if btype == "enc":
        S_eff = cfg.n_audio_frames
    x = jax.ShapeDtypeStruct((B, S_eff, D), dtype)
    x_sh = ctx.sharding(("batch", "seq_act", "embed_act"), x.shape)
    positions = jax.ShapeDtypeStruct((S_eff,), jnp.int32)
    pos_sh = NamedSharding(ctx.mesh, P())
    apply = BLOCKS[btype]["apply"]

    if shape.kind == "train":
        def fwd(p, xx, ex):
            return apply(p, cfg, xx, jnp.arange(S_eff), ex)[0]
        if cfg.parallel.remat == "block":
            fwd = jax.checkpoint(fwd)
        fn = jax.grad(
            lambda p, xx, ex: fwd(p, xx, ex).astype(jnp.float32).sum(),
            argnums=(0, 1))
        return _body_metrics(fn, (layer_p, x, extras_spec),
                             (p_sh, x_sh, extras_sh), parse_collectives)

    # prefill: forward + cache build (encoders have no prefill: plain apply)
    if BLOCKS[btype].get("prefill") is None:
        def fn(p, xx, pp, ex):
            return apply(p, cfg, xx, pp, ex)
    else:
        def fn(p, xx, pp, ex):
            return BLOCKS[btype]["prefill"](p, cfg, xx, pp, ex,
                                            shape.seq_len)

    return _body_metrics(fn, (layer_p, x, positions, extras_spec),
                         (p_sh, x_sh, pos_sh, extras_sh), parse_collectives)


def scan_corrections(cfg: ModelConfig, shape: InputShape, ctx: ShardingCtx,
                     parse_collectives) -> Tuple[Dict[str, float],
                                                 Dict[str, float]]:
    """Returns (extra, per_stage_detail): per-device metric deltas to add to
    the raw full-step numbers so scanned stages count ×trip instead of ×1."""
    extra = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    detail: Dict[str, float] = {}
    stages = list(stages_for(cfg))
    if cfg.is_encdec and shape.kind != "decode":
        stages.append(("enc", cfg.encoder_layers))
    seen: Dict[str, Dict[str, float]] = {}
    for btype, n in stages:
        if n <= 1:
            continue
        if btype not in seen:
            seen[btype] = stage_body_metrics(cfg, shape, ctx, btype,
                                             parse_collectives)
        m = seen[btype]
        for k in extra:
            extra[k] += (n - 1) * m[k]
        detail[f"{btype}_flops_per_layer"] = m["flops"]
    return extra, detail

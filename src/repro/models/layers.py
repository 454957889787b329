"""Shared primitive layers: norms, rotary embeddings, MLPs, initialisers.

All model code in this package is purely functional: params are plain pytrees
of jnp arrays, every layer is ``init(key, ...) -> params`` +
``apply(params, x, ...) -> y``.  Layer params are built *stacked* along a
leading layer axis by the model assembly (models/model.py) so whole stages
run under ``jax.lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def param_dtype(cfg):
    return jnp.dtype(cfg.dtype)


def trunc_normal(key, shape, scale=0.02, dtype=jnp.float32):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> jnp.ndarray:
    return jnp.ones((d,), dtype=dtype)


def rmsnorm(x: jnp.ndarray, weight, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm with fp32 accumulation; weight=None => non-parametric (OLMo)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


def layernorm_init(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(x, p, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if p is not None:
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    """Inverse frequencies for RoPE (host-side constant)."""
    half = head_dim // 2
    return 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate the last dim of ``x`` (..., T, n_heads, head_dim) by positions (T,) or (B,T)."""
    if theta <= 0:
        return x
    head_dim = x.shape[-1]
    inv = jnp.asarray(rope_frequencies(head_dim, theta), dtype=jnp.float32)
    pos = positions.astype(jnp.float32)
    ang = pos[..., None] * inv                      # (..., T, half)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    # broadcast over the head axis: x is (..., T, H, hd)
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n_pos: int, dim: int) -> np.ndarray:
    """Whisper-style sinusoidal embeddings (host-side constant)."""
    log_timescale = np.log(10_000.0) / (dim // 2 - 1)
    inv = np.exp(-log_timescale * np.arange(dim // 2))
    scaled = np.arange(n_pos)[:, None] * inv[None, :]
    return np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1).astype(np.float32)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": trunc_normal(k1, (d_model, d_ff), dtype=dtype),   # gate
        "w3": trunc_normal(k3, (d_model, d_ff), dtype=dtype),   # up
        "w2": trunc_normal(k2, (d_ff, d_model), dtype=dtype),   # down
    }


def mlp_apply(p, x):
    h = jax.nn.silu(jnp.einsum("...d,df->...f", x, p["w1"]))
    h = h * jnp.einsum("...d,df->...f", x, p["w3"])
    return jnp.einsum("...f,fd->...d", h, p["w2"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_init(key, vocab: int, d_model: int, dtype, scale: float = 0.02):
    return trunc_normal(key, (vocab, d_model), scale=scale, dtype=dtype)


def embed_apply(table, tokens):
    return jnp.take(table, tokens, axis=0)


def unembed_apply(table_or_head, x, tied: bool):
    if tied:
        return jnp.einsum("...d,vd->...v", x, table_or_head)
    return jnp.einsum("...d,dv->...v", x, table_or_head)

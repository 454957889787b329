"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

[arXiv:2405.04517] — faithful recurrences with exponential gating and
log-space stabilisation:

mLSTM (parallelisable matrix-memory LSTM):
    C_t = f_t C_{t-1} + i_t v_t k_t^T        (per head, C in R^{hd x hd})
    n_t = f_t n_{t-1} + i_t k_t
    h_t = (C_t q_t) / max(|n_t . q_t|, 1)
with m_t = max(log f_t + m_{t-1}, log i_t) stabilising i/f.

sLSTM (scalar-memory LSTM with recurrent head mixing):
    c_t = f c_{t-1} + i z_t ; n_t = f n_{t-1} + i ; h_t = o * c_t / n_t
with block-diagonal (per-head) recurrent weights R_{z,i,f,o}.

Both are time-sequential ``lax.scan``s (the recurrent form is also exactly
what decode needs); train_4k lowers as a scan so HLO stays O(1) in L.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .layers import rmsnorm, rmsnorm_init, trunc_normal


def _heads(cfg) -> Tuple[int, int]:
    return cfg.n_heads, cfg.resolved_head_dim


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg, dtype):
    D = cfg.d_model
    H, hd = _heads(cfg)
    inner = H * hd
    ks = jax.random.split(key, 8)
    return {
        "wq": trunc_normal(ks[0], (D, H, hd), dtype=dtype),
        "wk": trunc_normal(ks[1], (D, H, hd), dtype=dtype),
        "wv": trunc_normal(ks[2], (D, H, hd), dtype=dtype),
        "wi": trunc_normal(ks[3], (D, H), scale=0.01, dtype=dtype),
        "wf": trunc_normal(ks[4], (D, H), scale=0.01, dtype=dtype),
        "bf": jnp.ones((H,), jnp.float32) * 3.0,     # forget-gate bias >0
        "up_z": trunc_normal(ks[5], (D, inner), dtype=dtype),
        "down": trunc_normal(ks[6], (inner, D), dtype=dtype),
        "out_norm": rmsnorm_init(hd, dtype),
    }


def _mlstm_gates(p, cfg, x):
    """log-input/forget gates. x: (B,L,D) -> (B,L,H) fp32 each."""
    log_i = jnp.einsum("bld,dh->blh", x, p["wi"]).astype(jnp.float32)
    f_pre = jnp.einsum("bld,dh->blh", x, p["wf"]).astype(jnp.float32) + p["bf"]
    log_f = -jax.nn.softplus(-f_pre)                 # log sigmoid
    return log_i, log_f


def mlstm_state_init(cfg, batch: int, n_layers: int):
    H, hd = _heads(cfg)
    return {
        "C": jnp.zeros((n_layers, batch, H, hd, hd), jnp.float32),
        "n": jnp.zeros((n_layers, batch, H, hd), jnp.float32),
        "m": jnp.full((n_layers, batch, H), -1e30, jnp.float32),
    }


def _mlstm_step(qkv_t, log_i_t, log_f_t, state):
    """One recurrence step. qkv_t: (q,k,v) each (B,H,hd) fp32."""
    q, k, v = qkv_t
    C, n, m = state["C"], state["n"], state["m"]
    m_new = jnp.maximum(log_f_t + m, log_i_t)                 # (B,H)
    i_sc = jnp.exp(log_i_t - m_new)
    f_sc = jnp.exp(log_f_t + m - m_new)
    C = f_sc[..., None, None] * C + i_sc[..., None, None] * \
        (v[..., :, None] * k[..., None, :])                   # (B,H,hd,hd)
    n = f_sc[..., None] * n + i_sc[..., None] * k
    num = jnp.einsum("bhij,bhj->bhi", C, q)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhj,bhj->bh", n, q)), 1.0)
    h = num / den[..., None]
    return h, {"C": C, "n": n, "m": m_new}


def _chunked_scan(step, init, xs, length: int, chunk: int = 128):
    """lax.scan with gradient checkpointing at chunk boundaries.

    The naive per-timestep scan saves every step's carry for the backward
    pass — for the mLSTM's (B,H,hd,hd) matrix state over L=4096 that is
    ~68 GB/layer (measured 2.6 TB/chip on xlstm train_4k, §Perf X1).
    Chunking saves only boundary carries and recomputes inside each chunk;
    values are bit-identical.
    """
    c = min(chunk, length)
    n, r = divmod(length, c)

    def inner(carry, chunk_xs):
        return jax.lax.scan(step, carry, chunk_xs)

    take = jax.tree_util.tree_map(lambda a: a[: n * c], xs)
    chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((n, c) + a.shape[1:]), take)
    carry, hs = jax.lax.scan(jax.checkpoint(inner), init, chunked)
    hs = jax.tree_util.tree_map(
        lambda a: a.reshape((n * c,) + a.shape[2:]), hs)
    if r:
        rest = jax.tree_util.tree_map(lambda a: a[n * c:], xs)
        carry, hs_r = jax.lax.scan(step, carry, rest)
        hs = jnp.concatenate([hs, hs_r], axis=0)
    return carry, hs


def mlstm_apply(p, cfg, x, state=None, return_state: bool = False):
    """x: (B,L,D) -> (B,L,D)."""
    B, L, D = x.shape
    H, hd = _heads(cfg)
    scale = hd ** -0.5
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"]).astype(jnp.float32) * scale
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"]).astype(jnp.float32) * scale
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"]).astype(jnp.float32)
    log_i, log_f = _mlstm_gates(p, cfg, x)
    if state is None:
        st = jax.tree_util.tree_map(lambda a: a[0],
                                    mlstm_state_init(cfg, B, 1))
    else:
        st = state

    def step(carry, t):
        q_t, k_t, v_t, li_t, lf_t = t
        h, carry = _mlstm_step((q_t, k_t, v_t), li_t, lf_t, carry)
        return carry, h

    xs = (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2, 3),
          v.transpose(1, 0, 2, 3), log_i.transpose(1, 0, 2),
          log_f.transpose(1, 0, 2))
    st, hs = _chunked_scan(step, st, xs, L)
    h = hs.transpose(1, 0, 2, 3)                              # (B,L,H,hd)
    h = rmsnorm(h, p["out_norm"], cfg.norm_eps).astype(x.dtype)
    z = jax.nn.silu(jnp.einsum("bld,de->ble", x, p["up_z"]))
    out = jnp.einsum("ble,ed->bld", h.reshape(B, L, H * hd) * z, p["down"])
    if return_state:
        return out, st
    return out


def mlstm_decode(p, cfg, x, state):
    """x: (B,1,D). Returns (out (B,1,D), new state)."""
    B = x.shape[0]
    H, hd = _heads(cfg)
    scale = hd ** -0.5
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"]).astype(jnp.float32)[:, 0] * scale
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"]).astype(jnp.float32)[:, 0] * scale
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"]).astype(jnp.float32)[:, 0]
    log_i, log_f = _mlstm_gates(p, cfg, x)
    h, st = _mlstm_step((q, k, v), log_i[:, 0], log_f[:, 0], state)
    h = rmsnorm(h[:, None], p["out_norm"], cfg.norm_eps).astype(x.dtype)
    z = jax.nn.silu(jnp.einsum("bld,de->ble", x, p["up_z"]))
    out = jnp.einsum("ble,ed->bld", h.reshape(B, 1, H * hd) * z, p["down"])
    return out, st


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_init(key, cfg, dtype):
    D = cfg.d_model
    H, hd = _heads(cfg)
    ks = jax.random.split(key, 9)
    p = {
        "wz": trunc_normal(ks[0], (D, H, hd), dtype=dtype),
        "wi": trunc_normal(ks[1], (D, H, hd), scale=0.01, dtype=dtype),
        "wf": trunc_normal(ks[2], (D, H, hd), scale=0.01, dtype=dtype),
        "wo_g": trunc_normal(ks[3], (D, H, hd), dtype=dtype),
        "rz": trunc_normal(ks[4], (H, hd, hd), dtype=dtype),
        "ri": trunc_normal(ks[5], (H, hd, hd), scale=0.01, dtype=dtype),
        "rf": trunc_normal(ks[6], (H, hd, hd), scale=0.01, dtype=dtype),
        "ro": trunc_normal(ks[7], (H, hd, hd), dtype=dtype),
        "bf": jnp.ones((H, hd), jnp.float32) * 3.0,
        "down": trunc_normal(ks[8], (H * hd, D), dtype=dtype),
        "out_norm": rmsnorm_init(hd, dtype),
    }
    return p


def slstm_state_init(cfg, batch: int, n_layers: int):
    H, hd = _heads(cfg)
    z = jnp.zeros((n_layers, batch, H, hd), jnp.float32)
    return {"c": z, "n": z, "h": z, "m": jnp.full_like(z, -1e30)}


def _slstm_step(p, pre_t, state):
    """pre_t: dict of pre-activations (B,H,hd) fp32 (input-side only)."""
    c, n, h_prev, m = state["c"], state["n"], state["h"], state["m"]
    rec = lambda name: jnp.einsum("bhj,hji->bhi", h_prev,
                                  p[name].astype(jnp.float32))
    z = jnp.tanh(pre_t["z"] + rec("rz"))
    log_i = pre_t["i"] + rec("ri")
    f_pre = pre_t["f"] + rec("rf") + p["bf"]
    log_f = -jax.nn.softplus(-f_pre)
    o = jax.nn.sigmoid(pre_t["o"] + rec("ro"))
    m_new = jnp.maximum(log_f + m, log_i)
    i_sc = jnp.exp(log_i - m_new)
    f_sc = jnp.exp(log_f + m - m_new)
    c = f_sc * c + i_sc * z
    n = f_sc * n + i_sc
    h = o * c / jnp.maximum(n, 1.0)
    return h, {"c": c, "n": n, "h": h, "m": m_new}


def _slstm_preact(p, x):
    f32 = jnp.float32
    return {
        "z": jnp.einsum("bld,dhk->blhk", x, p["wz"]).astype(f32),
        "i": jnp.einsum("bld,dhk->blhk", x, p["wi"]).astype(f32),
        "f": jnp.einsum("bld,dhk->blhk", x, p["wf"]).astype(f32),
        "o": jnp.einsum("bld,dhk->blhk", x, p["wo_g"]).astype(f32),
    }


def slstm_apply(p, cfg, x, state=None, return_state: bool = False):
    B, L, D = x.shape
    H, hd = _heads(cfg)
    pre = _slstm_preact(p, x)
    if state is None:
        st = jax.tree_util.tree_map(lambda a: a[0],
                                    slstm_state_init(cfg, B, 1))
    else:
        st = state

    def step(carry, t):
        h, carry = _slstm_step(p, t, carry)
        return carry, h

    xs = jax.tree_util.tree_map(lambda a: a.transpose(1, 0, 2, 3), pre)
    st, hs = _chunked_scan(step, st, xs, L)
    h = hs.transpose(1, 0, 2, 3)
    h = rmsnorm(h, p["out_norm"], cfg.norm_eps).astype(x.dtype)
    out = jnp.einsum("ble,ed->bld", h.reshape(B, L, H * hd), p["down"])
    if return_state:
        return out, st
    return out


def slstm_decode(p, cfg, x, state):
    B = x.shape[0]
    H, hd = _heads(cfg)
    pre = _slstm_preact(p, x)
    pre_t = jax.tree_util.tree_map(lambda a: a[:, 0], pre)
    h, st = _slstm_step(p, pre_t, state)
    h = rmsnorm(h[:, None], p["out_norm"], cfg.norm_eps).astype(x.dtype)
    out = jnp.einsum("ble,ed->bld", h.reshape(B, 1, H * hd), p["down"])
    return out, st

def _batch_local(apply_fn, p, cfg, x, return_state: bool):
    """Run a recurrent apply under shard_map with batch fully local.

    Left to the SPMD partitioner, the backward of the per-timestep
    recurrence all-reduces the recurrent-weight gradients ONCE PER STEP
    (xlstm train_4k: 137 GB/step of in-loop dR all-reduces — §Perf X4).
    shard_map fences it: params replicate in, dR accumulates locally, and
    the single psum happens at the shard_map transpose boundary.
    """
    from ..sharding import active_ctx
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    ctx = active_ctx()
    if ctx is None or cfg.parallel.tensor_parallel:
        return apply_fn(p, cfg, x, return_state=return_state)
    spec = ctx.resolve(("batch", None, None), x.shape)
    if spec[0] is None:
        return apply_fn(p, cfg, x, return_state=return_state)
    out_specs = (spec, P(spec[0])) if return_state else spec

    def inner(p_, x_):
        return apply_fn(p_, cfg, x_, return_state=return_state)

    return shard_map(inner, mesh=ctx.mesh, in_specs=(P(), spec),
                     out_specs=out_specs, check_vma=False)(p, x)

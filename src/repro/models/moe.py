"""Mixture-of-Experts: top-k router + two expert-parallel dispatch modes.

``dense_onehot`` — GShard-style dispatch/combine einsums over a
(B, S, E, C) one-hot tensor. Simple, SPMD-friendly, but the mask scales with
E — used for small expert counts (phi3.5, E=16).

``sort_scatter`` — flatten tokens, argsort by expert id, scatter into an
(E, C, D) capacity-bucketed buffer, run experts batched, gather back with
the gate weights. O(N·K) memory independent of E — used for DeepSeek-V3
(E=256). Dropped tokens (over capacity) fall into a sacrificial row.

Both modes are pure pjit: the expert axis carries a sharding hint
('experts' -> 'model') and XLA SPMD inserts the all-to-alls. Equivalence of
the two modes is property-tested (tests/test_moe.py).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from ..sharding import hint
from .layers import trunc_normal


def moe_init(key, cfg, dtype):
    e = cfg.moe
    D = cfg.d_model
    ks = jax.random.split(key, 7)
    p = {
        "router": trunc_normal(ks[0], (D, e.n_experts), dtype=jnp.float32),
        "we1": trunc_normal(ks[1], (e.n_experts, D, e.d_ff_expert), dtype=dtype),
        "we3": trunc_normal(ks[2], (e.n_experts, D, e.d_ff_expert), dtype=dtype),
        "we2": trunc_normal(ks[3], (e.n_experts, e.d_ff_expert, D), dtype=dtype),
    }
    if e.n_shared_experts:
        f_sh = (e.d_ff_shared or e.d_ff_expert) * e.n_shared_experts
        p["ws1"] = trunc_normal(ks[4], (D, f_sh), dtype=dtype)
        p["ws3"] = trunc_normal(ks[5], (D, f_sh), dtype=dtype)
        p["ws2"] = trunc_normal(ks[6], (f_sh, D), dtype=dtype)
    return p


def router_topk(p, cfg, x) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (gates (B,S,K) normalised, experts (B,S,K) int32, aux_loss)."""
    e = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, e.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # load-balance aux loss: E * sum_e f_e * P_e   (Switch / GShard)
    E = e.n_experts
    chosen_onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)   # (B,S,K,E)
    f = jnp.mean(jnp.sum(chosen_onehot, axis=2), axis=(0, 1))        # (E,)
    P_mean = jnp.mean(probs, axis=(0, 1))                            # (E,)
    aux = E * jnp.sum(f * P_mean) * e.aux_loss_weight
    return gates, experts, aux


def _capacity(n_tokens: int, cfg) -> int:
    e = cfg.moe
    c = math.ceil(n_tokens * e.top_k / e.n_experts * e.capacity_factor)
    return max(8, -(-c // 8) * 8)      # round up to 8 (TPU sublane)


def _experts_ffn(p, h):
    """h: (E, C, D) -> (E, C, D) batched SwiGLU over the expert axis."""
    h = hint(h, "experts", None, None)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, p["we1"]))
    u = jnp.einsum("ecd,edf->ecf", h, p["we3"])
    out = jnp.einsum("ecf,efd->ecd", g * u, p["we2"])
    return hint(out, "experts", None, None)


def moe_apply_dense_onehot(p, cfg, x):
    """(B,S,D) -> (B,S,D). GShard dispatch over (B,S,E,C) one-hot masks."""
    e = cfg.moe
    B, S, D = x.shape
    gates, experts, aux = router_topk(p, cfg, x)      # (B,S,K)
    E = e.n_experts
    C = _capacity(S, cfg)                             # per batch row

    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)          # (B,S,K,E)
    # position of each (token, k) within its expert: s-major, k-minor priority
    # (matches the stable argsort order of the sort_scatter mode)
    flat = onehot.reshape(B, S * e.top_k, E)
    pos = jnp.cumsum(flat, axis=1) - flat                           # (B,SK,E)
    pos = pos.reshape(B, S, e.top_k, E).astype(jnp.int32)           # (B,S,K,E)
    keep = pos < C
    gk = gates[..., None] * onehot * keep                           # (B,S,K,E)
    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep[..., None]
    combine = jnp.einsum("bske,bskec->bsec", gk, pos_oh)            # (B,S,E,C)
    dispatch = (combine > 0).astype(x.dtype)

    expert_in = jnp.einsum("bsec,bsd->ebcd", dispatch, x)           # (E,B,C,D)
    expert_in = expert_in.reshape(E, B * C, D)
    expert_out = _experts_ffn(p, expert_in).reshape(E, B, C, D)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), expert_out)
    if e.n_shared_experts:
        y = y + _shared_ffn(p, x)
    return y, aux


def moe_apply_sort_scatter(p, cfg, x):
    """(B,S,D) -> (B,S,D). Sort-based capacity bucketing, O(N*K) memory."""
    e = cfg.moe
    B, S, D = x.shape
    gates, experts, aux = router_topk(p, cfg, x)
    N = B * S
    K = e.top_k
    E = e.n_experts
    C = _capacity(N, cfg)

    xf = x.reshape(N, D)
    expert_flat = experts.reshape(N * K)
    gate_flat = gates.reshape(N * K)
    token_idx = jnp.arange(N * K, dtype=jnp.int32) // K

    order = jnp.argsort(expert_flat)                  # stable
    sorted_e = expert_flat[order]
    counts = jnp.zeros((E,), jnp.int32).at[expert_flat].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_expert = jnp.arange(N * K, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_expert < C
    dest = jnp.where(keep, sorted_e * C + pos_in_expert, E * C)     # drop row

    buf = jnp.zeros((E * C + 1, D), x.dtype)
    buf = buf.at[dest].set(xf[token_idx[order]])
    expert_in = buf[: E * C].reshape(E, C, D)
    expert_out = _experts_ffn(p, expert_in).reshape(E * C, D)
    expert_out = jnp.concatenate(
        [expert_out, jnp.zeros((1, D), x.dtype)], axis=0)

    contrib = expert_out[dest] * gate_flat[order][:, None].astype(x.dtype)
    y = jnp.zeros((N, D), x.dtype).at[token_idx[order]].add(contrib)
    y = y.reshape(B, S, D)
    if e.n_shared_experts:
        y = y + _shared_ffn(p, x)
    return y, aux


def _shared_ffn(p, x):
    g = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["ws1"]))
    u = jnp.einsum("bsd,df->bsf", x, p["ws3"])
    return jnp.einsum("bsf,fd->bsd", g * u, p["ws2"])


# ---------------------------------------------------------------------------
# Expert-parallel all-to-all dispatch (shard_map)
# ---------------------------------------------------------------------------

def _local_bucket(xf, bucket_flat, n_buckets: int, C: int):
    """Sort-scatter ``xf`` (N,D) rows into (n_buckets, C, D) by bucket id.

    Returns (buf, order, dest): ``order`` is the stable sort order of rows
    by bucket, ``dest`` the flat slot each sorted row landed in (the drop
    row ``n_buckets*C`` when over capacity) — enough to invert the routing
    when combining.
    """
    N, D = xf.shape
    order = jnp.argsort(bucket_flat)                  # stable
    sorted_b = bucket_flat[order]
    counts = jnp.zeros((n_buckets,), jnp.int32).at[bucket_flat].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(N, dtype=jnp.int32) - starts[sorted_b]
    keep = pos < C
    dest = jnp.where(keep, sorted_b * C + pos, n_buckets * C)
    buf = jnp.zeros((n_buckets * C + 1, D), xf.dtype)
    buf = buf.at[dest].set(xf[order])
    return buf[: n_buckets * C].reshape(n_buckets, C, D), order, dest


def moe_apply_a2a(p, cfg, x, *, mesh, data_axes, model_axis="model"):
    """Expert parallelism with explicit all-to-alls under ``shard_map``.

    The pjit sort_scatter path scatters tokens into a global (E*C, D)
    buffer that SPMD can only combine with a full-buffer all-reduce
    (measured 110 TB/step on deepseek-v3 train_4k — EXPERIMENTS.md §Perf).
    Here each (data, model) shard routes a DISTINCT slice of tokens:
    bucket by destination model-shard -> all_to_all -> bucket by local
    expert -> expert FFN -> all_to_all back -> weighted combine.  When the
    residual stream is sequence-sharded the token slice is the seq shard;
    otherwise each shard slices its 1/n_sh of the flat tokens and the
    combined output is psum'd back to replicated.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    e = cfg.moe
    E = e.n_experts
    n_sh = mesh.shape[model_axis]
    E_loc = E // n_sh
    B, S, D = x.shape
    seq_sharded = bool(cfg.parallel.seq_parallel) and S % n_sh == 0
    d_axes = tuple(data_axes)

    x_spec = P(d_axes or None, model_axis if seq_sharded else None, None)
    w_e = P(model_axis, None, None)        # expert-sharded weights
    rep = P()

    def route_and_exchange(xf, router_w, we1, we3, we2):
        """xf: (N, D) — this shard's distinct tokens."""
        N = xf.shape[0]
        K = e.top_k
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, K)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
        f = jnp.mean(jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32),
                             axis=1), axis=0)
        aux = E * jnp.sum(f * jnp.mean(probs, axis=0)) * e.aux_loss_weight

        expert_flat = experts.reshape(N * K)
        gate_flat = gates.reshape(N * K).astype(xf.dtype)
        token_idx = jnp.arange(N * K, dtype=jnp.int32) // K
        xrep = xf[token_idx]                          # (N*K, D)

        # --- dispatch: bucket by destination model shard ---------------
        C_sh = _capacity(max(N * K // n_sh, 1), cfg)
        dest_shard = expert_flat // E_loc
        send, order, dest = _local_bucket(xrep, dest_shard, n_sh, C_sh)
        ids = jnp.full((n_sh * C_sh + 1,), -1, jnp.int32)
        ids = ids.at[dest].set((expert_flat % E_loc)[order])
        ids = ids[: n_sh * C_sh].reshape(n_sh, C_sh)

        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        recv_ids = jax.lax.all_to_all(ids, model_axis, split_axis=0,
                                      concat_axis=0, tiled=True)

        # --- run MY experts over the received tokens -------------------
        M = n_sh * C_sh
        rflat = recv.reshape(M, D)
        idflat = jnp.where(recv_ids.reshape(M) < 0, E_loc,
                           recv_ids.reshape(M))      # pads -> drop bucket
        C_loc = _capacity(max(M // max(E_loc, 1), 1), cfg)
        ebuf, eorder, edest = _local_bucket(rflat, idflat, E_loc + 1, C_loc)
        ein = ebuf[:E_loc]                            # (E_loc, C_loc, D)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, we1))
        u = jnp.einsum("ecd,edf->ecf", ein, we3)
        eout = jnp.einsum("ecf,efd->ecd", g * u, we2)
        # invert local bucketing: sorted row i came from rflat[eorder[i]]
        eflat = jnp.concatenate(
            [eout.reshape(E_loc * C_loc, D),
             jnp.zeros((C_loc + 1, D), eout.dtype)], axis=0)
        back = jnp.zeros((M, D), eout.dtype)
        back = back.at[eorder].set(eflat[edest])
        back = back.reshape(n_sh, C_sh, D)

        # --- return trip + weighted combine ----------------------------
        ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        retflat = jnp.concatenate(
            [ret.reshape(n_sh * C_sh, D),
             jnp.zeros((1, D), ret.dtype)], axis=0)
        contrib = retflat[dest] * gate_flat[order][:, None]
        y = jnp.zeros((N, D), xf.dtype).at[token_idx[order]].add(contrib)
        return y, aux

    if seq_sharded:
        def body(x_blk, router_w, we1, we3, we2):
            B_loc, S_loc, _ = x_blk.shape
            y, aux = route_and_exchange(x_blk.reshape(B_loc * S_loc, D),
                                        router_w, we1, we3, we2)
            return y.reshape(B_loc, S_loc, D), aux[None]
    else:
        def body(x_blk, router_w, we1, we3, we2):
            B_loc, S_loc, _ = x_blk.shape
            N_tot = B_loc * S_loc
            N = N_tot // n_sh
            mi = jax.lax.axis_index(model_axis)
            xf = jax.lax.dynamic_slice_in_dim(
                x_blk.reshape(N_tot, D), mi * N, N, axis=0)
            y_loc, aux = route_and_exchange(xf, router_w, we1, we3, we2)
            y = jnp.zeros((N_tot, D), y_loc.dtype)
            y = jax.lax.dynamic_update_slice_in_dim(y, y_loc, mi * N, axis=0)
            y = jax.lax.psum(y, model_axis)
            return y.reshape(B_loc, S_loc, D), aux[None]

    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, rep, w_e, w_e, w_e),
        out_specs=(x_spec, P(model_axis)),
        check_vma=False,
    )(x, p["router"], p["we1"], p["we3"], p["we2"])
    if e.n_shared_experts:
        y = y + _shared_ffn(p, x)
    return y, jnp.mean(aux)


def _a2a_applicable(cfg, x, ctx) -> bool:
    """a2a needs every shard to own an equal, non-empty token slice."""
    if ctx is None or "model" not in ctx.axis_sizes:
        return False
    n_sh = ctx.axis_sizes["model"]
    if n_sh <= 1 or cfg.moe.n_experts % n_sh:
        return False
    B, S, _ = x.shape
    n_data = 1
    for a in ("pod", "data"):
        n_data *= ctx.axis_sizes.get(a, 1)
    if B % n_data:
        return False
    B_loc = B // n_data
    if cfg.parallel.seq_parallel and S % n_sh == 0:
        return True
    return (B_loc * S) % n_sh == 0 and (B_loc * S) >= n_sh


def moe_apply(p, cfg, x):
    if cfg.moe.dispatch == "a2a":
        from ..sharding import active_ctx
        ctx = active_ctx()
        if _a2a_applicable(cfg, x, ctx):
            data_axes = tuple(a for a in ("pod", "data")
                              if a in ctx.axis_sizes)
            return moe_apply_a2a(p, cfg, x, mesh=ctx.mesh,
                                 data_axes=data_axes)
        # fallback (single device / tiny decode batches): pjit dispatch
        return moe_apply_sort_scatter(p, cfg, x)
    if cfg.moe.dispatch == "sort_scatter":
        return moe_apply_sort_scatter(p, cfg, x)
    return moe_apply_dense_onehot(p, cfg, x)

"""Plain reference of a dense GQA decoder (Llama-style), in float32.

Pre-norm blocks: RMSNorm (weight 1), attention with rotate-half RoPE and
grouped KV heads under a causal mask, residual add, RMSNorm, SwiGLU MLP
(silu(x W1) * x W3) W2, residual add; final RMSNorm and the tied
unembedding.  No kernel, no cache, no batching trick: every prompt and its
served tokens run as one causal sequence, layer by layer, rows in blocks.

Imports nothing of the program.  The weights are drawn again from the seed
by the configuration's stated rule (``assumed.weights``): the key tree and
truncated-normal draws of the program's initialiser, rounded to bfloat16,
the precision they are served in, then computed with in float32 at the
highest matmul precision.

``precision`` other than "f32" is the control: every matmul operand is
first rounded to int8 (symmetric, per row or channel of the contraction)
or to float8 e4m3 (scaled per row or channel) - the lower precision a
later change could be tempted to serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROW_BLOCK = 16


def _tn(key, shape):
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * 0.02
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _sizes(c: dict) -> Tuple[int, int, int, int, int, int, int]:
    return (c["hidden_size"], c["intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["vocab_size"], c["num_hidden_layers"])


@functools.partial(jax.jit, static_argnums=(1,))
def _embed_weights(key, sizes):
    D, _F, _H, _K, _hd, V, _L = sizes
    keys = jax.random.split(key, 8)
    return _tn(keys[0], (V, D)), keys[1]


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_weights(stage_key, i, sizes):
    D, F, H, K, hd, _V, L = sizes
    layer_key = jax.random.split(jax.random.fold_in(stage_key, 0), L)[i]
    k1, k2 = jax.random.split(layer_key)
    ks = jax.random.split(k1, 4)
    m1, m2, m3 = jax.random.split(k2, 3)
    return {"wq": _tn(ks[0], (D, H, hd)), "wk": _tn(ks[1], (D, K, hd)),
            "wv": _tn(ks[2], (D, K, hd)), "wo": _tn(ks[3], (H, hd, D)),
            "w1": _tn(m1, (D, F)), "w3": _tn(m3, (D, F)),
            "w2": _tn(m2, (F, D))}


def _round(x, axis, precision):
    """``x`` rounded to the control's precision, scaled over ``axis``."""
    if precision == "f32":
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if precision == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if precision == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(precision)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    half = hd // 2
    inv = jnp.asarray(1.0 / (theta ** (np.arange(0, half, dtype=np.float64)
                                       / half)), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv      # (B,S,half)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(h, w, sizes, eps_theta, precision):
    """One block over rows ``h`` (B,S,D), positions 0..S-1."""
    D, F, H, K, hd, _V, _L = sizes
    eps, theta = eps_theta
    q8 = functools.partial(_round, precision=precision)
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = q8(_rms(h, eps), -1)
    proj = lambda name, n: jnp.einsum(                       # noqa: E731
        "bsd,dn->bsn", x, q8(w[name].reshape(D, n), 0),
        precision=HIGHEST)
    q = _rope(proj("wq", H * hd).reshape(B, S, H, hd), pos, theta)
    k = _rope(proj("wk", K * hd).reshape(B, S, K, hd), pos, theta)
    v = proj("wv", K * hd).reshape(B, S, K, hd)
    G = H // K
    qg = q8(q.reshape(B, S, K, G, hd), -1)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, q8(k, -1),
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", q8(probs, -1), q8(v, 1),
                     precision=HIGHEST).reshape(B, S, H * hd)
    h = h + jnp.einsum("bsn,nd->bsd", q8(out, -1),
                       q8(w["wo"].reshape(H * hd, D), 0), precision=HIGHEST)
    x = q8(_rms(h, eps), -1)
    a = jnp.einsum("bsd,df->bsf", x, q8(w["w1"], 0), precision=HIGHEST)
    b = jnp.einsum("bsd,df->bsf", x, q8(w["w3"], 0), precision=HIGHEST)
    m = q8(jax.nn.silu(a) * b, -1)
    return h + jnp.einsum("bsf,fd->bsd", m, q8(w["w2"], 0), precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits(h, embed, idx, eps, precision):
    """Logits at positions ``idx`` (B,N) of rows ``h`` (B,S,D)."""
    hs = jnp.take_along_axis(h, idx[..., None], axis=1)
    x = _round(_rms(hs, eps), -1, precision)
    return jnp.einsum("bnd,vd->bnv", x, _round(embed, -1, precision),
                      precision=HIGHEST)


def served_logits(c: dict, weight_seed: int,
                  prompts: Sequence[Sequence[int]],
                  served: Sequence[Sequence[int]],
                  precisions: Sequence[str] = ("f32",)
                  ) -> Dict[str, List[np.ndarray]]:
    """For each request, the logits (n_served, V) that predict each of its
    served tokens from its prompt and the served tokens before it, in each
    of ``precisions``."""
    sizes = _sizes(c)
    D, V, L = sizes[0], sizes[5], sizes[6]
    eps_theta = (float(c["rms_norm_eps"]), float(c["rope_theta"]))
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    S = max(8, -(-max(len(s) for s in seqs) // 8) * 8)
    N = max(len(s) for s in served)
    out: Dict[str, List[np.ndarray]] = {}
    with jax.default_matmul_precision("highest"):
        embed, stage_key = _embed_weights(jax.random.PRNGKey(weight_seed),
                                          sizes)
        for prec in precisions:
            rows = []
            for lo in range(0, len(seqs), ROW_BLOCK):
                block = seqs[lo:lo + ROW_BLOCK]
                toks = np.zeros((ROW_BLOCK, S), np.int32)
                idx = np.zeros((ROW_BLOCK, N), np.int32)
                for i, sq in enumerate(block):
                    toks[i, :len(sq)] = sq
                    p = len(prompts[lo + i])
                    n = len(served[lo + i])
                    idx[i, :n] = np.arange(p - 1, p - 1 + n)
                rows.append((block, jnp.take(embed, jnp.asarray(toks), 0),
                             idx))
            hs = [r[1] for r in rows]
            for i in range(L):
                w = _layer_weights(stage_key, i, sizes)
                hs = [_layer(h, w, sizes, eps_theta, prec) for h in hs]
            res = []
            for (block, _h0, idx), h in zip(rows, hs):
                lg = np.asarray(_logits(h, embed, jnp.asarray(idx),
                                        eps_theta[0], prec))
                for i in range(len(block)):
                    res.append(lg[i, :len(served[len(res)])])
            out[prec] = res
    return out

"""Plain reference of Granite's decoder (``GraniteForCausalLM``), in float32.

The dense GQA block of ``dense_gqa`` with Granite's four scalar
multipliers, read from the configuration, where Granite applies them:

    h_0     = E[ids] * embedding_multiplier
    a_l     = Attn(RMSNorm(h_l)),  softmax(q k^T * attention_multiplier) v
    h_l'    = h_l  + residual_multiplier * a_l
    h_{l+1} = h_l' + residual_multiplier * MLP(RMSNorm(h_l'))
    logits  = RMSNorm(h_L) E^T / logits_scaling

Attention is causal with rotate-half RoPE and grouped KV heads; the MLP is
SwiGLU, (silu(x W1) * x W3) W2; every RMSNorm has weight 1; the
embedding ``E`` is tied.  Departures from the published model: no dropout
(``attention_dropout`` is a training key), and random weights.

Imports nothing of the program.  The weights are those of ``dense_gqa``,
drawn again from the seed by the configuration's stated rule, but for the
embedding table: drawn at 0.02 / embedding_multiplier, so that the scaled
rows ``h_0`` start at the initialiser's scale.  (At 0.02, random weights
make the tied head repeat the input token by a margin no rounding can
cross, and the served tokens would show neither a lower precision nor a
dropped multiplier.)  Rounded to bfloat16, then computed with in float32
at the highest matmul precision, every prompt and its served tokens as
one causal sequence, rows in blocks.  ``precision`` other than "f32" is ``dense_gqa``'s control: every
matmul operand first rounded to int8 or to float8 e4m3.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dense_gqa import (HIGHEST, ROW_BLOCK, _layer_weights, _rms, _rope,
                        _round, _sizes)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _embed_weights(key, sizes, m_emb):
    """The tied table, at the initialiser's scale over the multiplier, and
    the key of the layers."""
    D, _F, _H, _K, _hd, V, _L = sizes
    keys = jax.random.split(key, 8)
    x = jax.random.truncated_normal(keys[0], -2.0, 2.0, (V, D), jnp.float32)
    return (x * (0.02 / m_emb)).astype(jnp.bfloat16).astype(jnp.float32), \
        keys[1]


def _multipliers(c: dict):
    return (float(c["embedding_multiplier"]), float(c["residual_multiplier"]),
            float(c["attention_multiplier"]), float(c["logits_scaling"]))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(h, w, sizes, eps_theta, res_attn, precision):
    """One block over rows ``h`` (B,S,D), positions 0..S-1."""
    D, F, H, K, hd, _V, _L = sizes
    eps, theta = eps_theta
    m_res, m_attn = res_attn
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
                        precision=HIGHEST) * m_attn
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkh->bskgh", q8(probs, -1), q8(v, 1),
                     precision=HIGHEST).reshape(B, S, H * hd)
    h = h + m_res * jnp.einsum("bsn,nd->bsd", q8(out, -1),
                               q8(w["wo"].reshape(H * hd, D), 0),
                               precision=HIGHEST)
    x = q8(_rms(h, eps), -1)
    a = jnp.einsum("bsd,df->bsf", x, q8(w["w1"], 0), precision=HIGHEST)
    b = jnp.einsum("bsd,df->bsf", x, q8(w["w3"], 0), precision=HIGHEST)
    m = q8(jax.nn.silu(a) * b, -1)
    return h + m_res * jnp.einsum("bsf,fd->bsd", m, q8(w["w2"], 0),
                                  precision=HIGHEST)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _logits(h, embed, idx, eps, logits_scaling, precision):
    """Logits at positions ``idx`` (B,N) of rows ``h`` (B,S,D)."""
    hs = jnp.take_along_axis(h, idx[..., None], axis=1)
    x = _round(_rms(hs, eps), -1, precision)
    return jnp.einsum("bnd,vd->bnv", x, _round(embed, -1, precision),
                      precision=HIGHEST) / logits_scaling


def served_logits(c: dict, weight_seed: int,
                  prompts: Sequence[Sequence[int]],
                  served: Sequence[Sequence[int]],
                  precisions: Sequence[str] = ("f32",)
                  ) -> Dict[str, List[np.ndarray]]:
    """For each request, the logits (n_served, V) that predict each of its
    served tokens from its prompt and the served tokens before it, in each
    of ``precisions``."""
    sizes = _sizes(c)
    L = sizes[6]
    eps_theta = (float(c["rms_norm_eps"]), float(c["rope_theta"]))
    m_emb, m_res, m_attn, s_logits = _multipliers(c)
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    S = max(8, -(-max(len(s) for s in seqs) // 8) * 8)
    N = max(len(s) for s in served)
    out: Dict[str, List[np.ndarray]] = {}
    with jax.default_matmul_precision("highest"):
        embed, stage_key = _embed_weights(jax.random.PRNGKey(weight_seed),
                                          sizes, m_emb)
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
                rows.append((block, jnp.take(embed, jnp.asarray(toks), 0)
                             * m_emb, idx))
            hs = [r[1] for r in rows]
            for i in range(L):
                w = _layer_weights(stage_key, i, sizes)
                hs = [_layer(h, w, sizes, eps_theta, (m_res, m_attn), prec)
                      for h in hs]
            res = []
            for (block, _h0, idx), h in zip(rows, hs):
                lg = np.asarray(_logits(h, embed, jnp.asarray(idx),
                                        eps_theta[0], s_logits, prec))
                for i in range(len(block)):
                    res.append(lg[i, :len(served[len(res)])])
            out[prec] = res
    return out

"""Single-token GQA decode attention Pallas TPU kernels (dense + paged).

Decode attention is memory-bound: the whole KV cache streams HBM->VMEM once
while compute is a (G x bk) @ (bk x hd) matmul per block — arithmetic
intensity ~G. The dense kernel therefore:

- tiles over (B, K, T/bk): one program per (batch, kv-head), sequential over
  KV blocks, all G grouped q-heads processed together so each KV tile is
  read exactly ONCE (the GQA bandwidth win — a naive per-q-head kernel would
  read the cache G times);
- carries the online-softmax state (m, l, acc) in fp32 VMEM scratch;
- masks ring slots >= n_valid[b] ((B,) vector in SMEM, indexed by the batch
  program — each row of a persistent slot pool is masked at its OWN length,
  so a dynamic batch with ragged prefixes decodes in one kernel launch).

The PAGED kernel (``decode_attention_paged_pallas``) reads a physical page
pool (n_pages, P, K, hd) through a per-row (B, max_pages) int32 page table
instead of a dense (B, T) cache slice: the table rides in as a
scalar-prefetch argument (``pltpu.PrefetchScalarGridSpec``) so the KV
BlockSpec index_map can pick each program's physical page —
``table[b, ki]`` — before the kernel body runs.  Refcounted shared-prefix
pages are thus gathered per-row at DMA time with zero data duplication
(vLLM's PagedAttention access pattern).  It reads the pool in the layout
it is stored in, with no transpose of the pool outside the kernel:

- grid (B, max_pages): one program per (row, logical page), sequential
  over the row's pages;
- K/V blocks (1, P, K, hd): one whole physical page with ALL K heads;
  logical pages past a row's last live page map to that live page again,
  so a dead step repeats the block index and starts no DMA;
- q/out blocks (1, H, hd) per row; the page is read as (P*K, hd) rows,
  token-major, and one MXU pass scores every q-head against every row,
  keeping the pairs whose KV head is the q-head's own (q-head h*G + g
  reads KV head h) — MHA (G == 1) and GQA (G > 1) run the same body, G
  and K read from the shapes;
- the online-softmax state per q-head in fp32 VMEM scratch: m, l (H, 1),
  acc (H, hd); scores and p·V accumulate in fp32 and neither dot rounds
  an operand (``_exact``).

Blocks: the dense kernel reads ``bk`` = 256 ring slots per step, or the
whole ring when 256 does not divide it (a block equal to the array dim is
always a legal TPU tile).  Its q/out block is the full (G, hd) group, so
MHA (G == 1) runs unpadded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(n_valid_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: float, bk: int, n_kv_blocks: int):
    bi = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = n_valid_ref[bi]
    block_live = ki * bk < n_valid

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n_valid, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_pallas(q, k, v, n_valid, *, softcap: float = 0.0,
                            scale: float | None = None, bk: int = 256,
                            interpret: bool = False):
    """q: (B,1,H,hd); k,v: (B,T,K,hd); n_valid int32 scalar or (B,)."""
    B, Sq, H, hd = q.shape
    assert Sq == 1, "decode kernel is single-token"
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    bk = min(bk, T)
    if T % bk:
        bk = T                                         # whole ring, one block
    n_kv_blocks = T // bk

    qg = q.reshape(B, K, G, hd)                        # group q-heads by kv head
    kt = k.transpose(0, 2, 1, 3)                       # (B,K,T,hd)
    vt = v.transpose(0, 2, 1, 3)
    n_valid_arr = jnp.asarray(n_valid, jnp.int32)
    if n_valid_arr.ndim == 0:
        n_valid_arr = jnp.full((B,), n_valid_arr, jnp.int32)
    assert n_valid_arr.shape == (B,), n_valid_arr.shape

    grid = (B, K, n_kv_blocks)
    kern = functools.partial(_kernel, scale=scale, softcap=softcap, bk=bk,
                             n_kv_blocks=n_kv_blocks)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(n_valid_arr, qg, kt, vt)
    return out.reshape(B, 1, H, hd)


def _exact(dtype):
    """Precision of an f32 dot whose operands hold ``dtype`` values that
    rounds none of them: the MXU's default single bf16 pass is exact for
    bf16 values (f32 products, f32 accumulation); wider values need the
    multi-pass fp32 contraction."""
    return None if dtype == jnp.bfloat16 else jax.lax.Precision.HIGHEST


def _paged_kernel(n_valid_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, softcap: float):
    # the page table is consumed by the BlockSpec index_maps (the DMA-time
    # gather); the body sees one whole page of row b with all K heads
    del table_ref
    bi = pl.program_id(0)
    ki = pl.program_id(1)
    _, P, K, hd = k_ref.shape
    H = q_ref.shape[1]
    G = H // K

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    n_valid = n_valid_ref[bi]

    @pl.when(ki * P < n_valid)
    def _compute():
        # the page as stored, token-major: row t*K + h is token t, head h
        k = k_ref[0].astype(jnp.float32).reshape(P * K, hd)
        v = v_ref[0].astype(jnp.float32).reshape(P * K, hd)
        # every q-head against every (token, head) row on the MXU; the
        # pairs across heads are masked, which costs K x the FLOPs of the
        # scores but no relayout of the page
        s = jax.lax.dot_general(
            q_ref[0].astype(jnp.float32), k, (((1,), (1,)), ((), ())),
            precision=_exact(jnp.promote_types(q_ref.dtype, k_ref.dtype)),
            preferred_element_type=jnp.float32) * scale
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        own = col % K == row // G                     # q-head h*G+g -> h
        live = ki * P + col // K < n_valid
        s = jnp.where(own & live, s, NEG_INF)         # (H, P*K)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                            precision=_exact(p.dtype),
                                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_paged_pallas(q, k_pages, v_pages, page_table, n_valid, *,
                                  softcap: float = 0.0,
                                  scale: float | None = None,
                                  interpret: bool = False):
    """q: (B,1,H,hd); k_pages/v_pages: (n_pages,P,K,hd) physical pools;
    page_table: (B,max_pages) int32 (entries < 0 = unmapped, clamped to the
    reserved trash page 0 — always masked by n_valid); n_valid int32 scalar
    or (B,).  Row b's logical ring is its mapped pages back to back."""
    B, Sq, H, hd = q.shape
    assert Sq == 1, "decode kernel is single-token"
    P, K = k_pages.shape[1], k_pages.shape[2]
    assert H % K == 0, (H, K)
    max_pages = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(hd)

    table = jnp.maximum(jnp.asarray(page_table, jnp.int32), 0)
    n_valid_arr = jnp.asarray(n_valid, jnp.int32)
    if n_valid_arr.ndim == 0:
        n_valid_arr = jnp.full((B,), n_valid_arr, jnp.int32)
    assert n_valid_arr.shape == (B,), n_valid_arr.shape
    assert table.shape == (B, max_pages)

    def kv_index(b, ki, nv, tbl):
        # the paged gather: row b's ki-th logical page, clamped to its last
        # live page so that a dead step repeats the block index and starts
        # no DMA
        last = jnp.maximum(nv[b] - 1, 0) // P
        return (tbl[b, jnp.minimum(ki, last)], 0, 0, 0)

    kern = functools.partial(_paged_kernel, scale=scale, softcap=softcap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # n_valid + page table in SMEM
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, ki, nv, tbl: (b, 0, 0)),
            pl.BlockSpec((1, P, K, hd), kv_index),
            pl.BlockSpec((1, P, K, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, ki, nv, tbl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret,
    )(n_valid_arr, table, q.reshape(B, H, hd), k_pages, v_pages)
    return out.reshape(B, 1, H, hd)

"""Selective-SSM chunked scan Pallas TPU kernel.

The scan is time-sequential but memory-bound; the TPU adaptation is the
HBM->VMEM *chunking*, not warp-level parallelism (the GPU Mamba kernel's
shared-memory/warp tricks have no analogue here — see DESIGN.md):

- grid = (B, L/chunk) with the chunk axis sequential ("arbitrary"), so the
  fp32 state h (DI, N) lives in VMEM scratch across chunks and HBM traffic
  is exactly one read of x/dt/B/C and one write of y per token;
- inside a chunk, a fori_loop steps the recurrence on VMEM-resident tiles;
  all per-step tensors are (DI, N) VREG-friendly outer products;
- the final state is written once by the last chunk (needed to seed decode).

VMEM budget: x/dt tiles 2*chunk*DI*2B + B/C tiles 2*chunk*N*4B + h DI*N*4B;
for DI=3200, N=16, chunk=128 that is ~1.9 MB — comfortably inside the
~16 MB/core VMEM envelope, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, hout_ref,
            h_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = A_ref[...].astype(jnp.float32)                 # (DI, N)
    D = D_ref[...].astype(jnp.float32)                 # (1, DI)

    def step(t, h):
        x_t = x_ref[0, t].astype(jnp.float32)          # (DI,)
        dt_t = jax.nn.softplus(dt_ref[0, t].astype(jnp.float32))
        B_t = B_ref[0, t].astype(jnp.float32)          # (N,)
        C_t = C_ref[0, t].astype(jnp.float32)          # (N,)
        dA = jnp.exp(dt_t[:, None] * A)                # (DI, N)
        h = dA * h + (dt_t * x_t)[:, None] * B_t[None, :]
        y_t = jnp.sum(h * C_t[None, :], axis=-1) + D[0] * x_t
        y_ref[0, t] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == n_chunks - 1)
    def _final():
        hout_ref[0] = h


def ssm_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 128,
                    interpret: bool = False):
    """x, dt: (Bt,L,DI); A: (DI,N); B, C: (Bt,L,N); D: (DI,).

    Returns (y (Bt,L,DI), h_final (Bt,DI,N) fp32).  A length ``chunk`` does
    not divide is scanned as one chunk."""
    Bt, L, DI = x.shape
    N = A.shape[1]
    chunk = L if L % min(chunk, L) else min(chunk, L)
    n_chunks = L // chunk
    grid = (Bt, n_chunks)
    kern = functools.partial(_kernel, chunk=chunk, n_chunks=n_chunks)
    y, h_final = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, DI), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, DI), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((DI, N), lambda b, ci: (0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, DI), lambda b, ci: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, DI), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, DI, N), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bt, L, DI), x.dtype),
            jax.ShapeDtypeStruct((Bt, DI, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((DI, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, jnp.asarray(B), jnp.asarray(C), D.reshape(1, DI))
    return y, h_final

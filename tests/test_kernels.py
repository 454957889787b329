"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_paged_pallas, decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_paged_ref,
                                                decode_attention_ref,
                                                gather_pages_ref)
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.ssm_scan import ops as ssm_ops
from repro.kernels.ssm_scan.ssm_scan import ssm_scan_pallas
from repro.kernels.ssm_scan.ref import ssm_scan_ref, ssm_step_ref


def _qkv(key, B, S, T, H, K, hd, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype)
    k = jax.random.normal(kk, (B, T, K, hd), dtype)
    v = jax.random.normal(kv, (B, T, K, hd), dtype)
    return q, k, v


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,S,T,H,K,hd", [
        (1, 128, 128, 4, 4, 64),       # MHA square
        (2, 128, 128, 8, 2, 64),       # GQA 4:1
        (1, 256, 256, 4, 1, 128),      # MQA, MXU-aligned head
        (1, 128, 256, 4, 2, 64),       # cross-length (cache longer)
    ])
    def test_sweep_vs_ref(self, dtype, B, S, T, H, K, hd):
        q, k, v = _qkv(jax.random.PRNGKey(0), B, S, T, H, K, hd, dtype)
        out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   **TOL[dtype])

    @pytest.mark.parametrize("window", [128, 256])
    def test_sliding_window(self, window):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 384, 384, 4, 4, 64,
                       jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                     interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 128, 128, 2, 2, 64,
                       jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, softcap=30.0,
                                     interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 128, 2, 2, 64,
                       jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_dispatch_unaligned_falls_back(self):
        # a CPU program lowers the reference at any length, odd ones too
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 100, 100, 2, 2, 64,
                       jnp.float32)
        out = flash_attention(q, k, v, causal=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)


class TestDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,T,H,K,hd", [
        (1, 256, 4, 4, 64),
        (2, 256, 8, 2, 64),
        (1, 512, 16, 2, 128),
    ])
    def test_sweep_vs_ref(self, dtype, B, T, H, K, hd):
        q, k, v = _qkv(jax.random.PRNGKey(5), B, 1, T, H, K, hd, dtype)
        for n_valid in (T // 4, T):
            nv = jnp.asarray(n_valid, jnp.int32)
            out = decode_attention_pallas(q, k, v, nv, interpret=True)
            ref = decode_attention_ref(q, k, v, nv)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       **TOL[dtype])

    def test_vector_n_valid_ragged_rows(self):
        """(B,) n_valid — each slot-pool row masked at its OWN length:
        pallas-interpret vs ref parity, and each row must equal a scalar
        single-row call at that row's length."""
        B, T, H, K, hd = 4, 256, 4, 2, 64
        q, k, v = _qkv(jax.random.PRNGKey(7), B, 1, T, H, K, hd, jnp.float32)
        nv = jnp.asarray([17, 256, 64, 1], jnp.int32)
        out = decode_attention_pallas(q, k, v, nv, interpret=True)
        ref = decode_attention_ref(q, k, v, nv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        for i in range(B):
            solo = decode_attention_ref(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                        nv[i])
            np.testing.assert_allclose(
                np.asarray(ref[i]), np.asarray(solo[0]), rtol=2e-5,
                atol=2e-5, err_msg=f"row {i} != scalar call at its length")

    def test_vector_n_valid_softcap(self):
        q, k, v = _qkv(jax.random.PRNGKey(8), 2, 1, 256, 4, 2, 64,
                       jnp.float32)
        nv = jnp.asarray([40, 200], jnp.int32)
        out = decode_attention_pallas(q, k, v, nv, softcap=30.0,
                                      interpret=True)
        ref = decode_attention_ref(q, k, v, nv, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_flash_on_full_prefix(self):
        """decode(q_last) == flash(q_full)[:, -1] when the cache holds the
        same prefix — the consistency the serving path relies on."""
        B, S, H, K, hd = 1, 128, 4, 2, 64
        q, k, v = _qkv(jax.random.PRNGKey(6), B, S, S, H, K, hd, jnp.float32)
        full = flash_attention_ref(q, k, v, causal=True)
        one = decode_attention_ref(q[:, -1:], k, v,
                                   jnp.asarray(S, jnp.int32))
        np.testing.assert_allclose(np.asarray(one[:, 0]),
                                   np.asarray(full[:, -1]),
                                   rtol=1e-5, atol=1e-5)


def _paged(key, B, n_pages, P, max_pages, H, K, hd, dtype,
           share_first=0):
    """Random page pools + a page table mapping each row to distinct
    pages (optionally aliasing the first ``share_first`` pages across
    every row, the shared-prefix shape).  Page 0 stays trash."""
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 1, H, hd), dtype)
    kp = jax.random.normal(kk, (n_pages, P, K, hd), dtype)
    vp = jax.random.normal(kv, (n_pages, P, K, hd), dtype)
    table = np.zeros((B, max_pages), np.int32)
    nxt = 1 + share_first
    for b in range(B):
        table[b, :share_first] = range(1, share_first + 1)
        for j in range(share_first, max_pages):
            table[b, j] = nxt
            nxt += 1
    assert nxt <= n_pages
    return q, kp, vp, jnp.asarray(table)


class TestPagedDecodeAttention:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("B,P,max_pages,H,K,hd", [
        (1, 128, 2, 4, 4, 64),
        (2, 128, 4, 8, 2, 64),
        (2, 256, 2, 4, 2, 128),
        (2, 64, 7, 32, 32, 64),         # the smollm2 cell's geometry (MHA)
        (2, 64, 3, 32, 8, 128),         # Granite-3.0-8B's GQA widths
    ])
    def test_sweep_vs_ref_and_dense(self, dtype, B, P, max_pages, H, K, hd):
        """Pallas-interpret == paged ref == dense ref over the gathered
        ring, for scalar n_valid at several fills."""
        T = P * max_pages
        q, kp, vp, table = _paged(jax.random.PRNGKey(11), B,
                                  1 + B * max_pages, P, max_pages, H, K, hd,
                                  dtype)
        for n_valid in (P // 2, T // 2, T):
            nv = jnp.asarray(n_valid, jnp.int32)
            out = decode_attention_paged_pallas(q, kp, vp, table, nv,
                                                interpret=True)
            ref = decode_attention_paged_ref(q, kp, vp, table, nv)
            dense = decode_attention_ref(q, gather_pages_ref(kp, table),
                                         gather_pages_ref(vp, table), nv)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32),
                                       **TOL[dtype])
            np.testing.assert_allclose(np.asarray(ref, np.float32),
                                       np.asarray(dense, np.float32),
                                       **TOL[dtype])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_granite_attention_multiplier(self, dtype):
        """Granite's widths (K = 8, G = 4, hd 128) with its softmax scale,
        ``attention_multiplier`` 1/128 in place of 1/sqrt(128): the grouped
        body applies the scale it is given, as the reference does."""
        B, P, max_pages, H, K, hd = 2, 64, 3, 32, 8, 128
        q, kp, vp, table = _paged(jax.random.PRNGKey(16), B,
                                  1 + B * max_pages, P, max_pages, H, K, hd,
                                  dtype)
        nv = jnp.asarray([P + 9, P * max_pages], jnp.int32)
        out = decode_attention_paged_pallas(q, kp, vp, table, nv,
                                            scale=1 / 128, interpret=True)
        ref = decode_attention_paged_ref(q, kp, vp, table, nv, scale=1 / 128)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), **TOL[dtype])
        unscaled = decode_attention_paged_ref(q, kp, vp, table, nv)
        assert np.abs(np.asarray(ref, np.float32)
                      - np.asarray(unscaled, np.float32)).max() > 0.1

    def test_vector_n_valid_shared_pages(self):
        """(B,) per-row lengths over a table whose first page is ALIASED
        across rows (shared prefix): parity, and each row must equal a
        single-row dense call over its own gathered ring."""
        B, P, max_pages, H, K, hd = 4, 128, 3, 4, 2, 64
        q, kp, vp, table = _paged(jax.random.PRNGKey(12), B,
                                  1 + 1 + B * max_pages, P, max_pages, H, K,
                                  hd, jnp.float32, share_first=1)
        nv = jnp.asarray([P - 7, P * max_pages, P + 1, 1], jnp.int32)
        out = decode_attention_paged_pallas(q, kp, vp, table, nv,
                                            interpret=True)
        ref = decode_attention_paged_ref(q, kp, vp, table, nv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        dense_k = gather_pages_ref(kp, table)
        dense_v = gather_pages_ref(vp, table)
        for i in range(B):
            solo = decode_attention_ref(q[i:i + 1], dense_k[i:i + 1],
                                        dense_v[i:i + 1], nv[i])
            np.testing.assert_allclose(
                np.asarray(ref[i]), np.asarray(solo[0]), rtol=2e-5,
                atol=2e-5, err_msg=f"row {i} != its own gathered ring")

    @pytest.mark.parametrize("H,K,hd", [(32, 32, 64), (32, 8, 128)])
    def test_vector_n_valid_trailing_trash(self, H, K, hd):
        """Rows end mid-page and their trailing table entries are 0 (the
        trash page), as the decoder leaves them: the kernel's dead steps
        repeat the last live page and must add nothing."""
        B, P, max_pages = 3, 64, 4
        q, kp, vp, table = _paged(jax.random.PRNGKey(15), B,
                                  1 + B * max_pages, P, max_pages, H, K, hd,
                                  jnp.float32)
        nv = np.asarray([P + 5, 3 * P - 1, 17], np.int32)
        tbl = np.asarray(table).copy()
        for b, n in enumerate(nv):
            tbl[b, -(-n // P):] = 0
        kp = kp.at[0].set(999.0)
        out = decode_attention_paged_pallas(q, kp, vp, jnp.asarray(tbl),
                                            jnp.asarray(nv), interpret=True)
        ref = decode_attention_paged_ref(q, kp, vp, jnp.asarray(tbl),
                                         jnp.asarray(nv))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_unmapped_pages_inert(self):
        """Entries past the valid length (0 = trash sentinel) must not
        leak into the output: scribbling on the trash page and on the
        unmapped tail pages changes nothing."""
        B, P, max_pages, H, K, hd = 2, 128, 3, 4, 2, 64
        q, kp, vp, table = _paged(jax.random.PRNGKey(13), B,
                                  1 + B * max_pages, P, max_pages, H, K, hd,
                                  jnp.float32)
        tbl = np.asarray(table).copy()
        tbl[:, -1] = 0                          # last logical page unmapped
        nv = jnp.asarray([P, 2 * P], jnp.int32)   # valid stops before it
        base = decode_attention_paged_ref(q, kp, vp, jnp.asarray(tbl), nv)
        unmapped = np.unique(np.asarray(table)[:, -1])
        kp2 = kp.at[0].set(999.0).at[unmapped].set(-999.0)
        out = decode_attention_paged_ref(q, kp2, vp, jnp.asarray(tbl), nv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=1e-6, atol=1e-6)

    def test_softcap(self):
        B, P, max_pages, H, K, hd = 2, 128, 2, 4, 2, 64
        q, kp, vp, table = _paged(jax.random.PRNGKey(14), B,
                                  1 + B * max_pages, P, max_pages, H, K, hd,
                                  jnp.float32)
        nv = jnp.asarray([40, 200], jnp.int32)
        out = decode_attention_paged_pallas(q, kp, vp, table, nv,
                                            softcap=30.0, interpret=True)
        ref = decode_attention_paged_ref(q, kp, vp, table, nv, softcap=30.0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


class TestSSMScan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("Bt,L,DI,N,chunk", [
        (1, 128, 64, 8, 32),
        (2, 256, 128, 16, 64),
        (1, 64, 256, 16, 64),
    ])
    def test_sweep_vs_ref(self, dtype, Bt, L, DI, N, chunk):
        key = jax.random.PRNGKey(7)
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (Bt, L, DI), dtype)
        dt = jax.random.normal(ks[1], (Bt, L, DI), dtype) * 0.1
        A = -jnp.abs(jax.random.normal(ks[2], (DI, N), jnp.float32)) - 0.1
        B = jax.random.normal(ks[3], (Bt, L, N), dtype)
        C = jax.random.normal(ks[4], (Bt, L, N), dtype)
        D = jnp.ones((DI,), jnp.float32) * 0.5
        y, h = ssm_scan_pallas(x, dt, A, B, C, D, chunk=chunk,
                               interpret=True)
        y_ref, h_ref = ssm_scan_ref(x, dt, A, B, C, D)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32),
                                   **TOL[dtype])
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-3, atol=1e-3)

    def test_scan_equals_stepwise(self):
        """Chunked scan == token-by-token recurrence (decode consistency)."""
        Bt, L, DI, N = 1, 32, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(8), 5)
        x = jax.random.normal(ks[0], (Bt, L, DI), jnp.float32)
        dt = jax.random.normal(ks[1], (Bt, L, DI), jnp.float32) * 0.1
        A = -jnp.abs(jax.random.normal(ks[2], (DI, N), jnp.float32)) - 0.1
        B = jax.random.normal(ks[3], (Bt, L, N), jnp.float32)
        C = jax.random.normal(ks[4], (Bt, L, N), jnp.float32)
        D = jnp.ones((DI,), jnp.float32)
        y_scan, h_scan = ssm_scan_ref(x, dt, A, B, C, D)
        h = jnp.zeros((Bt, DI, N), jnp.float32)
        ys = []
        for t in range(L):
            y_t, h = ssm_step_ref(x[:, t], dt[:, t], A, B[:, t], C[:, t],
                                  D, h)
            ys.append(y_t)
        y_step = jnp.stack(ys, axis=1)
        np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_step),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(h_scan), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)


class TestPlatformDispatch:
    """ops.py stages the kernel for TPU programs and the reference for
    every other platform; a length the preferred block does not divide
    is tiled as one whole block instead of falling back."""

    def test_flash_untiled_length_is_one_block(self):
        q, k, v = _qkv(jax.random.PRNGKey(20), 1, 136, 136, 4, 2, 64,
                       jnp.float32)
        out = flash_attention_pallas(q, k, v, causal=True, interpret=True)
        ref = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_decode_untiled_ring_is_one_block(self):
        q, k, v = _qkv(jax.random.PRNGKey(21), 2, 1, 384, 4, 2, 64,
                       jnp.float32)
        nv = jnp.asarray([300, 384], jnp.int32)
        out = decode_attention_pallas(q, k, v, nv, interpret=True)
        ref = decode_attention_ref(q, k, v, nv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_ssm_untiled_length_is_one_chunk(self):
        ks = jax.random.split(jax.random.PRNGKey(22), 5)
        Bt, L, DI, N = 1, 96, 64, 8
        x = jax.random.normal(ks[0], (Bt, L, DI), jnp.float32)
        dt = jax.random.normal(ks[1], (Bt, L, DI), jnp.float32) * 0.1
        A = -jnp.abs(jax.random.normal(ks[2], (DI, N), jnp.float32)) - 0.1
        B = jax.random.normal(ks[3], (Bt, L, N), jnp.float32)
        C = jax.random.normal(ks[4], (Bt, L, N), jnp.float32)
        D = jnp.ones((DI,), jnp.float32)
        y, h = ssm_scan_pallas(x, dt, A, B, C, D, chunk=64, interpret=True)
        y_ref, h_ref = ssm_scan_ref(x, dt, A, B, C, D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4)

    def test_cpu_program_lowers_reference(self):
        q, k, v = _qkv(jax.random.PRNGKey(23), 1, 1, 256, 4, 2, 64,
                       jnp.float32)
        nv = jnp.asarray([100], jnp.int32)
        text = jax.jit(decode_ops.decode_attention).lower(q, k, v,
                                                          nv).as_text()
        assert "tpu_custom_call" not in text
        np.testing.assert_allclose(
            np.asarray(decode_ops.decode_attention(q, k, v, nv)),
            np.asarray(decode_attention_ref(q, k, v, nv)), rtol=1e-6,
            atol=1e-6)

    @pytest.mark.parametrize("op", ["flash", "ssm"])
    def test_grad_is_reference_grad(self, op):
        """Training differentiates through the dispatch: the gradient is
        the reference's on every platform (the kernels have no bwd)."""
        if op == "flash":
            q, k, v = _qkv(jax.random.PRNGKey(24), 1, 128, 128, 4, 2, 64,
                           jnp.float32)
            got = jax.grad(lambda q: flash_attention(q, k, v).sum())(q)
            want = jax.grad(lambda q: flash_attention_ref(q, k, v).sum())(q)
        else:
            ks = jax.random.split(jax.random.PRNGKey(25), 3)
            x = jax.random.normal(ks[0], (1, 32, 16), jnp.float32)
            dt = jax.random.normal(ks[1], (1, 32, 16), jnp.float32) * 0.1
            A = -jnp.ones((16, 4), jnp.float32)
            Bm = jax.random.normal(ks[2], (1, 32, 4), jnp.float32)
            D = jnp.ones((16,), jnp.float32)

            def loss(fn, x):
                return fn(x, dt, A, Bm, Bm, D)[0].sum()

            got = jax.grad(functools.partial(loss, ssm_ops.ssm_scan))(x)
            want = jax.grad(functools.partial(loss, ssm_scan_ref))(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

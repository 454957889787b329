"""Bring-up check: the served path on one TPU at smollm2-1.7b's widths.

    python chip_smoke.py

One process, seeded data and weights, nothing outside the repository.
Phases, in order; any failure raises and exits non-zero before the result:

1. platform — the first JAX device must be a TPU;
2. kernels  — dense decode, paged decode and flash prefill, compiled for the
   chip at the served shapes, each against a float32 jnp reference;
3. serve    — 16 claims on 2 workers through ``serve.serve`` (Gateway →
   Scheduler → LiveExecutor → StreamingDecoder with paged KV → the paged
   Pallas decode kernel); every request must finish with 8 in-vocab tokens;
4. reference — 3 of those requests re-decoded by the full-forward path
   (``slot_cached=False``) must give the same greedy tokens, or the
   reference logits at the first divergence must put the served token
   within a stated bf16 tolerance of the top.

The last line of standard output is the result, as one JSON object.
"""
from __future__ import annotations

import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
N_CLAIMS = 16
N_WORKERS = 2
N_REFERENCE = 3
HBM_LIMIT = 16e9                 # one TPU v5e chip

# Kernel outputs are bf16 attention averages of N(0,1) values, |o| < 4.
# Storing one in bf16 moves it by at most half an ulp (2^-7 below 4), and
# the kernel's MXU pass rounds the f32 softmax weights to bf16 (2^-9
# relative, times |v| < 4); the sum is < 1.6e-2.  A wrong mask, page or
# block moves outputs by O(0.1-1), so 3e-2 separates the two.
KERNEL_TOL = {
    "dense decode": 3e-2,
    "paged decode": 3e-2,
    "flash prefill": 3e-2,
}
# Logits leave the model in bf16 (8 significant bits).  Two computations
# of one logit that differ only in accumulation order (paged decode kernel
# against the full forward's flash kernel) land a few ulps apart, so at a
# divergence the served token must sit within 4 ulps of the reference's top
# logit: a near tie, not a wrong cache.
LOGIT_ULPS = 4


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require(ok: bool, msg) -> None:
    """A failed check ends the run (unlike ``assert``, kept under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def kernel_cases(cfg, batch: int, prompt_len: int, max_len: int,
                 page_size: int):
    """(name, op, reference, args) for each main-path kernel at the served
    shapes: the contiguous slot ring, the paged pool of a ``batch``-row
    decoder whose rows share their first (prefix) page, and the prompt."""
    from repro.kernels.decode_attention import ops as decode_ops
    from repro.kernels.decode_attention.ref import (
        decode_attention_paged_ref, decode_attention_ref)
    from repro.kernels.flash_attention import ops as flash_ops
    from repro.kernels.flash_attention.ref import flash_attention_ref

    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED), 10)
    B, T = batch, max_len
    q1 = _normal(ks[0], (B, 1, H, hd))
    dense = (q1, _normal(ks[1], (B, T, K, hd)), _normal(ks[2], (B, T, K, hd)),
             jax.random.randint(ks[3], (B,), 1, T + 1, jnp.int32))
    max_pages = -(-T // page_size)
    n_pages = 1 + B * max_pages                      # + the trash page
    table = np.zeros((B, max_pages), np.int32)
    table[:, 0] = 1                                  # shared prefix page
    table[:, 1:] = 2 + np.random.default_rng(SEED).permutation(
        B * (max_pages - 1)).reshape(B, max_pages - 1)
    paged = (q1, _normal(ks[4], (n_pages, page_size, K, hd)),
             _normal(ks[5], (n_pages, page_size, K, hd)), jnp.asarray(table),
             jax.random.randint(ks[6], (B,), 1, max_pages * page_size + 1,
                                jnp.int32))
    S = prompt_len
    flash = (_normal(ks[7], (B, S, H, hd)), _normal(ks[8], (B, S, K, hd)),
             _normal(ks[9], (B, S, K, hd)))
    return [
        ("dense decode", decode_ops.decode_attention, decode_attention_ref,
         dense),
        ("paged decode", decode_ops.decode_attention_paged,
         decode_attention_paged_ref, paged),
        ("flash prefill", flash_ops.flash_attention, flash_attention_ref,
         flash),
    ]


def run_kernel(op, ref, args) -> dict:
    """Compile ``op`` for the default device, run it, and compare it with
    ``ref`` evaluated in float32 at the highest matmul precision."""
    t0 = time.perf_counter()
    lowered = jax.jit(op).lower(*args)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    out = np.asarray(jax.block_until_ready(compiled(*args)), np.float32)
    f32 = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
           else a for a in args]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref)(*f32), np.float32)
    return {"custom_call": "tpu_custom_call" in lowered.as_text(),
            "compile_s": compile_s, "finite": bool(np.isfinite(out).all()),
            "max_err": float(np.max(np.abs(out - want)))}


def check_kernels(cfg, *, batch: int, prompt_len: int, max_len: int,
                  page_size: int) -> None:
    for name, op, ref, args in kernel_cases(cfg, batch, prompt_len, max_len,
                                            page_size):
        r = run_kernel(op, ref, args)
        tol = KERNEL_TOL[name]
        log(f"kernel {name}: shapes {[tuple(a.shape) for a in args]} "
            f"tpu_custom_call={r['custom_call']} compile_s={r['compile_s']} "
            f"max_err={r['max_err']} tol={tol}")
        require(r["finite"], f"{name}: non-finite output")
        require(r["max_err"] <= tol, f"{name}: error {r['max_err']} > {tol}")
        require(r["custom_call"],
                f"{name}: no tpu_custom_call in the compiled program")


def check_served(run, n_claims: int) -> None:
    """Every request finished, with MAX_NEW tokens inside the vocab."""
    from repro.inference import MAX_NEW
    records = run.app.records()
    outcomes = [r.outcome for r in records]
    done = outcomes.count("done")
    per_worker = {}
    for r in records:
        per_worker[r.worker_id] = per_worker.get(r.worker_id, 0) + 1
    log(f"serve: requests done/sent {done}/{len(run.app.requests)} "
        f"outcomes {sorted(set(outcomes))} per worker {per_worker} "
        f"of {len(run.sched.workers)} workers")
    require(len(run.app.requests) == n_claims, len(run.app.requests))
    require(done == n_claims and len(outcomes) == n_claims, outcomes)
    V = run.cfg.vocab_size
    for r in run.app.requests:
        toks = run.ex.results[r.request_id]
        require(len(toks) == MAX_NEW and all(0 <= t < V for t in toks),
                (r.request_id, toks))


def _hosted(run):
    """Payloads of a library that served the run (same seeded weights on
    every worker)."""
    for w in run.sched.workers.values():
        for lib in w.libraries.values():
            if lib.ready and "_stream_decoder" in lib.context.payloads:
                return lib.context.payloads
    raise RuntimeError("no hosted library holds a stream decoder")


def check_reference(run, n: int) -> None:
    """Re-decode ``n`` served requests with the full-forward path."""
    from repro.inference import MAX_NEW, StreamingDecoder
    from repro.data.tokenizer import PAD
    payloads = _hosted(run)
    params = payloads["weights"]
    ci = payloads["context_inputs"]
    ref = StreamingDecoder(run.cfg, params, ci["tokenizer"], ci["template"],
                           slot_cached=False)
    reqs = sorted(run.app.requests, key=lambda r: r.request_id)[:n]
    rids = [r.request_id for r in reqs]
    for r in reqs:
        ref.ensure(r.request_id, r.payload)
    prompts = {rid: list(ref._tokens[rid]) for rid in rids}
    got = {rid: [] for rid in rids}
    for _ in range(MAX_NEW):
        for rid, t in ref.step(rids).items():
            got[rid].append(t)
    for rid in rids:
        served = list(run.ex.results[rid])
        if served == got[rid]:
            log(f"reference: request {rid} served == full forward "
                f"{served}")
            continue
        j = next(i for i, (a, b) in enumerate(zip(served, got[rid]))
                 if a != b)
        seq = prompts[rid] + served[:j]
        arr = np.full((1, -(-len(seq) // 8) * 8), PAD, np.int32)
        arr[0, :len(seq)] = seq
        logits = np.asarray(ref._fwd(params, arr)[0, len(seq) - 1],
                            np.float32)
        top = float(logits.max())
        tol = LOGIT_ULPS * 2.0 ** (math.floor(math.log2(abs(top))) - 7)
        gap = top - float(logits[served[j]])
        log(f"reference: request {rid} diverges at step {j}: served "
            f"{served[j]} ref {got[rid][j]}; reference logit gap {gap} "
            f"(top {top}) tol {tol}")
        require(gap <= tol, f"request {rid}: served token {gap} below top")


def served_kernels(run) -> dict:
    """Whether the programs the served path compiled contain each kernel:
    the decoder's paged ``decode_step``, and the engine's prefill (flash)
    and greedy loop (dense decode) that materialising the context warms."""
    payloads = _hosted(run)
    dec = payloads["_stream_decoder"]
    engine = payloads["xla_executable"]
    cap = dec.pool.capacity
    toks = jax.ShapeDtypeStruct((cap, 1), jnp.int32)
    mask = jax.ShapeDtypeStruct((cap,), jnp.bool_)
    decode = dec._decode.lower(dec.params, dec._cache, toks, mask)
    batch = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    prefill = engine._prefill.lower(engine.params, batch)
    _, cache = jax.eval_shape(engine._prefill, engine.params, batch)
    loop = engine._greedy_loop(1).lower(
        engine.params, cache, jax.ShapeDtypeStruct((1,), jnp.int32))
    return {name: "tpu_custom_call" in low.as_text() for name, low in
            (("paged decode_step", decode), ("engine prefill", prefill),
             ("engine greedy decode", loop))}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] needs a TPU; found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.configs import get_config
    from repro.inference import PROMPT_LEN
    from repro.launch import serve
    cache_dir = serve.configure_compile_cache()
    devices = jax.devices()
    log(f"device kind {dev.device_kind!r} count {len(devices)} "
        f"compile cache {cache_dir}")
    cfg = get_config("smollm2-1.7b")
    t0 = time.perf_counter()
    check_kernels(cfg, batch=8, prompt_len=PROMPT_LEN,
                  max_len=PROMPT_LEN + 64, page_size=64)
    log(f"kernels: {time.perf_counter() - t0} s")

    args = serve.parse_args(["--claims", str(N_CLAIMS), "--workers",
                             str(N_WORKERS), "--stream"])
    run = serve.serve(args)
    serve.report(run)
    log(f"serve: set-up (materialise + compile) {run.ex.staging_s} s, "
        f"wall {run.wall_s} s")
    check_served(run, N_CLAIMS)
    found = served_kernels(run)
    log(f"served programs tpu_custom_call: {found}")
    require(all(found.values()), found)
    t0 = time.perf_counter()
    check_reference(run, N_REFERENCE)
    log(f"reference: {time.perf_counter() - t0} s")
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak} bytes_limit {stats.get('bytes_limit')}")
    require(peak is not None and peak < HBM_LIMIT, peak)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Per-architecture smoke tests: reduced variant, one forward/train step on
CPU, output shapes + no NaNs; prefill+decode consistency with the full
forward (the strongest cheap correctness check a serving stack has)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_REGISTRY, ASSIGNED_ARCHS, get_config,
                           get_smoke_config)
from repro.models import model as M
from repro.optim import adamw_init
from repro.launch.steps import make_train_step

ALL_ARCHS = ASSIGNED_ARCHS + ["smollm2-1.7b"]


def _batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(4, cfg.vocab_size, (B, S)), jnp.int32)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_vision_patches, 1024)), cfg.dtype)
    if cfg.is_encdec:
        batch["audio_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_audio_frames, cfg.d_model)), cfg.dtype)
    return batch


@pytest.fixture(scope="module")
def smoke(request):
    return None


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestArchSmoke:
    def test_forward_shapes_no_nans(self, arch):
        cfg = get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        batch = _batch(cfg)
        logits = M.forward(cfg, params, batch)
        B, S = batch["tokens"].shape
        n_prefix = cfg.n_vision_patches if cfg.family == "vlm" else 0
        assert logits.shape == (B, S + n_prefix, cfg.vocab_size)
        assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())

    def test_train_step(self, arch):
        cfg = get_smoke_config(arch)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        step = make_train_step(cfg)
        p2, opt2, metrics = step(params, opt, _batch(cfg))
        assert np.isfinite(float(metrics["loss"]))
        assert int(opt2.step) == 1
        # params actually moved
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).max()),
            params, p2)
        assert max(jax.tree_util.tree_leaves(moved)) > 0

    def test_prefill_decode_consistency(self, arch):
        """prefill(S)+decode(t) logits == forward(S+t) last-token logits."""
        cfg = get_smoke_config(arch).with_(dtype="float32")
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        B, S, EXTRA = 2, 12, 3
        batch = _batch(cfg, B=B, S=S + EXTRA, seed=1)
        full_logits = M.forward(cfg, params, batch)

        n_prefix = cfg.n_vision_patches if cfg.family == "vlm" else 0
        pre = {k: (v[:, :S] if k == "tokens" else v)
               for k, v in batch.items()}
        logits_p, cache = M.prefill(cfg, params, pre,
                                    max_len=n_prefix + S + EXTRA + 4)
        np.testing.assert_allclose(
            np.asarray(logits_p[:, -1]),
            np.asarray(full_logits[:, -1 - EXTRA]), rtol=2e-3, atol=2e-3)
        logits_d = logits_p
        for t in range(EXTRA):
            logits_d, cache = M.decode_step(
                cfg, params, cache, batch["tokens"][:, S + t:S + t + 1])
            np.testing.assert_allclose(
                np.asarray(logits_d[:, -1]),
                np.asarray(full_logits[:, S + t
                                       + (cfg.n_vision_patches
                                          if cfg.family == "vlm" else 0)]),
                rtol=2e-3, atol=2e-3,
                err_msg=f"{arch}: decode step {t} diverges from forward")


def test_registry_complete():
    assert set(ASSIGNED_ARCHS) <= set(ARCH_REGISTRY)
    assert len(ASSIGNED_ARCHS) == 10


@pytest.mark.parametrize("arch,nl,dm,nh,nkv,dff,vocab", [
    ("llava-next-34b", 60, 7168, 56, 8, 20480, 64000),
    ("granite-3-8b", 40, 4096, 32, 8, 12800, 49155),
    ("llama3-405b", 126, 16384, 128, 8, 53248, 128256),
    ("qwen3-1.7b", 28, 2048, 16, 8, 6144, 151936),
    ("hymba-1.5b", 32, 1600, 25, 5, 5504, 32001),
    ("xlstm-350m", 24, 1024, 4, 4, 0, 50304),
    ("whisper-small", 12, 768, 12, 12, 3072, 51865),
    ("phi3.5-moe-42b-a6.6b", 32, 4096, 32, 8, 6400, 32064),
    # deepseek: the assigned d_ff=2048 is the EXPERT hidden dim (checked in
    # test_arch_specific_features); cfg.d_ff=18432 is the dense-head dim
    ("deepseek-v3-671b", 61, 7168, 128, 128, 18432, 129280),
    ("olmo-1b", 16, 2048, 16, 16, 8192, 50304),
])
def test_assigned_dims_exact(arch, nl, dm, nh, nkv, dff, vocab):
    cfg = get_config(arch)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (nl, dm, nh, nkv, dff, vocab)
    assert cfg.source, f"{arch} must cite its source"


def test_arch_specific_features():
    assert get_config("qwen3-1.7b").qk_norm
    assert get_config("olmo-1b").nonparametric_norm
    assert get_config("deepseek-v3-671b").mla is not None
    ds = get_config("deepseek-v3-671b").moe
    assert ds.n_experts == 256 and ds.top_k == 8 and ds.n_shared_experts == 1
    assert ds.d_ff_expert == 2048          # the assigned d_ff
    phi = get_config("phi3.5-moe-42b-a6.6b").moe
    assert phi.n_experts == 16 and phi.top_k == 2
    assert get_config("hymba-1.5b").hybrid_parallel_heads
    assert get_config("xlstm-350m").block_pattern
    assert get_config("whisper-small").is_encdec
    assert get_config("llava-next-34b").n_vision_patches > 0


def test_smoke_variant_bounds():
    for arch in ALL_ARCHS:
        s = get_smoke_config(arch)
        assert s.n_layers <= 2 or s.block_pattern
        assert s.d_model <= 512
        if s.moe:
            assert s.moe.n_experts <= 4


def test_param_counts_plausible():
    """n_params() within 20% of the published totals."""
    expect = {"llama3-405b": 405e9, "deepseek-v3-671b": 671e9,
              "granite-3-8b": 8e9, "qwen3-1.7b": 1.7e9, "olmo-1b": 1.1e9,
              "phi3.5-moe-42b-a6.6b": 42e9}
    for arch, n in expect.items():
        got = get_config(arch).n_params()
        assert abs(got - n) / n < 0.25, f"{arch}: {got/1e9:.1f}B vs {n/1e9}B"
    active = get_config("phi3.5-moe-42b-a6.6b").n_active_params()
    assert abs(active - 6.6e9) / 6.6e9 < 0.3


def test_int8_kv_cache_decode_close():
    """§Perf G5: int8 cache halves decode memory; logits stay argmax-true."""
    import jax
    import jax.numpy as jnp

    cfg = get_smoke_config("granite-3-8b").with_(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(4, cfg.vocab_size, (2, 20)), jnp.int32)
    pre = {"tokens": toks[:, :16]}
    _, cache = M.prefill(cfg, params, pre, max_len=24)
    cfg8 = cfg.with_(kv_cache_dtype="int8")
    _, cache8 = M.prefill(cfg8, params, pre, max_len=24)
    assert cache8["stages"][0]["k"].dtype == jnp.int8
    for t in range(3):
        ld, cache = M.decode_step(cfg, params, cache, toks[:, 16 + t:17 + t])
        ld8, cache8 = M.decode_step(cfg8, params, cache8,
                                    toks[:, 16 + t:17 + t])
        assert float(jnp.abs(ld - ld8).max()) < 0.05
        assert bool((jnp.argmax(ld, -1) == jnp.argmax(ld8, -1)).all())


@pytest.mark.parametrize("family", ["moe", "ssm", "hybrid", "vlm", "audio"])
def test_multipliers_only_on_the_dense_block(family):
    """Granite's scalar multipliers are applied by the dense block alone;
    any other family refuses them, so no model silently runs without."""
    base = get_config("granite-3-8b")
    assert base.family == "dense" and base.residual_multiplier == 0.22
    with pytest.raises(ValueError, match="dense block only"):
        base.with_(family=family)
    plain = base.with_(embedding_multiplier=1.0, residual_multiplier=1.0,
                       attention_multiplier=0.0, logits_scaling=1.0)
    assert plain.with_(family=family).family == family

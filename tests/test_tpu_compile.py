"""Compile-only guards for one TPU v5e chip at smollm2-1.7b's widths.

Nothing runs: each program is compiled for a DESCRIBED v5e chip (the TPU
compiler is installed even where no chip is attached), which is where
tiling, VMEM and HBM limits are enforced.  The kernel choice follows the
platform a program is compiled for, so each compiled program must contain
its Pallas kernel (``tpu_custom_call``), and the full-width serving steps
must fit the chip's 16 GB.

The topology is described only inside a module fixture: describing it
loads the TPU library, which one process at a time may hold.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from repro.configs import get_config
from repro.inference import PROMPT_LEN
from repro.models import model as M

HBM_BYTES = 16e9
B = 16                        # slot-pool capacity the decoder grows to
PAGE = 64
MAX_LEN = PROMPT_LEN + 64     # the streaming decoder's ring
MAX_PAGES = -(-MAX_LEN // PAGE)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def cfg():
    return get_config("smollm2-1.7b")


def _specs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("name", ["dense decode", "paged decode",
                                  "flash prefill"])
def test_kernel_compiles_into_program(one_chip, cfg, name):
    """The kernels at the shapes ``chip_smoke.py`` runs them on the chip."""
    op, args = {n: (op, args) for n, op, _ref, args in chip_smoke.kernel_cases(
        cfg, B, PROMPT_LEN, MAX_LEN, PAGE)}[name]
    compiled = jax.jit(op).lower(*_specs(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _paged_state(cfg, sharding):
    n_pages = 1 + B * MAX_PAGES
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        M.paged_cache_init, cfg, B, n_pages, PAGE, MAX_PAGES))
    return _specs(params, sharding), _specs(cache, sharding)


def _fits(compiled) -> None:
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, ma


def test_full_width_decode_step(one_chip, cfg):
    params, cache = _paged_state(cfg, one_chip)
    toks = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
    compiled = jax.jit(functools.partial(M.decode_step, cfg)).lower(
        params, cache, toks, mask).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the paged kernel reads the pool as stored, (n_pages, P, K, hd): no
    # instruction may produce the heads-major (n_pages, K, P, hd) copy
    transposed = (f"bf16[{1 + B * MAX_PAGES},{cfg.n_kv_heads},{PAGE},"
                  f"{cfg.resolved_head_dim}]")
    assert not [ln for ln in text.splitlines()
                if f"= {transposed}" in ln], transposed
    _fits(compiled)


def test_full_width_prefill_into_pages(one_chip, cfg):
    params, cache = _paged_state(cfg, one_chip)
    Bn = 8
    S = jax.ShapeDtypeStruct
    rows = S((Bn,), jnp.int32, sharding=one_chip)
    batch = {"tokens": S((Bn, PROMPT_LEN), jnp.int32, sharding=one_chip)}
    compiled = jax.jit(functools.partial(M.prefill_into_pages, cfg)).lower(
        params, batch, cache, rows, rows, rows).compile()
    _fits(compiled)


@pytest.mark.parametrize("program", ["decode_step", "prefill_into_pages"])
def test_granite_stage_fits_one_chip(one_chip, program):
    """Granite-3.0-8B's 10-layer stage at its published widths, at the
    shapes of the PfF sweep drafted for it: 32 slots of 9 pages, a 32-row
    admission of 464 tokens.  Decode holds the grouped paged kernel."""
    cfg = get_config("granite-3-8b").with_(n_layers=10)
    rows, pages = 32, 9
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        M.paged_cache_init, cfg, rows, 1 + rows * pages, PAGE, pages))
    params, cache = _specs(params, one_chip), _specs(cache, one_chip)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    if program == "decode_step":
        compiled = jax.jit(functools.partial(M.decode_step, cfg)).lower(
            params, cache, S((rows, 1), jnp.int32),
            S((rows,), jnp.bool_)).compile()
        assert "tpu_custom_call" in compiled.as_text()
    else:
        r = S((rows,), jnp.int32)
        compiled = jax.jit(functools.partial(M.prefill_into_pages, cfg)).lower(
            params, {"tokens": S((rows, 464), jnp.int32)}, cache, r, r,
            r).compile()
    _fits(compiled)


# the PfF cells' decoders: 32 slots; smollm2 rings of 7 pages (448
# tokens), Granite's 10-layer stage rings of 9 (576)
CELLS = {"smollm2-1.7b": (None, 7), "granite-3-8b": (10, 9)}


@pytest.mark.parametrize("arch, rows", [
    ("smollm2-1.7b", 64), ("smollm2-1.7b", 192),
    ("granite-3-8b", 64), ("granite-3-8b", 256)])
def test_chunk_grid_prefill_fits_one_chip(one_chip, arch, rows):
    """The paged admission's page-wide chunk grid at its published widths:
    64 rows, a 32-request cohort of the sweep, and the largest grid the
    cell's warm-up admits, 32 whole prompts of 6 (smollm2) or 8 (Granite)
    pages."""
    n_layers, pages = CELLS[arch]
    cfg = get_config(arch)
    if n_layers:
        cfg = cfg.with_(n_layers=n_layers)
    slots = 32
    params = jax.eval_shape(lambda k: M.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(functools.partial(
        M.paged_cache_init, cfg, slots, 1 + slots * pages, PAGE, pages))
    params, cache = _specs(params, one_chip), _specs(cache, one_chip)
    S = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    r = S((rows,), jnp.int32)
    compiled = jax.jit(functools.partial(M.prefill_into_pages, cfg)).lower(
        params, {"tokens": S((rows, PAGE), jnp.int32)}, cache, r, r,
        r).compile()
    _fits(compiled)

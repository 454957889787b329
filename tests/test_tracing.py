"""The decoder's host spans and launch counters under a profiler trace, and
the names of its jitted programs.

Each launch span (``repro.decoder.launch``) carries what the step computed
(program, real and padded rows and tokens, the page pool's pages in use
and reserved) and ``new_shape``, 1 exactly when the call met a shape the
decoder had not run before.
"""
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.inference.streaming import StreamingDecoder
from repro.models import model as M


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm2-1.7b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk(cfg, params, **kw):
    kw.setdefault("max_len", 24)
    kw.setdefault("page_size", 8)
    return StreamingDecoder(cfg, params, None, None, prompt_len=24, **kw)


def traced(tmp_path, fn):
    """Run ``fn`` under a profiler trace; its ``repro.*`` host spans as
    (name, start, end, stats), in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                                "*.xplane.pb")))[-1]
    out = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: s[1])


def launches(spans):
    return [st for n, _s, _e, st in spans if n == "repro.decoder.launch"]


def test_the_cluster_layer_imports_no_jax():
    """The executor's spans import JAX at their first use, so the
    simulator, which shares the cluster layer, still runs without it."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = "import sys, repro.cluster; sys.exit('jax' in sys.modules)"
    done = subprocess.run([sys.executable, "-c", code], timeout=120,
                          env=dict(os.environ, PYTHONPATH=src))
    assert done.returncode == 0


def test_jitted_programs_carry_their_names(setup):
    cfg, params = setup
    dec = _mk(cfg, params)
    names = {f.__name__ for f in (dec._fwd, dec._decode, dec._prefill_pages,
                                  dec._copy_page)}
    assert names == {"forward", "decode_step", "prefill_into_pages",
                     "copy_page"}
    dec.ensure_tokens(0, list(range(4, 14)))
    dec.step([0])
    toks = np.zeros((dec.pool.capacity, 1), np.int32)
    mask = np.ones((dec.pool.capacity,), bool)
    text = dec._decode.lower(params, dec._cache, toks, mask).as_text()
    assert text.startswith("module @jit_decode_step ")


def test_paged_launches_count_rows_tokens_pages_and_new_shapes(
        setup, tmp_path):
    """Two tenants share a 16-token prefix (2 pages of 8) and decode past
    the 24-token ring, so their writes wrap into the shared pages."""
    cfg, params = setup
    rng = np.random.default_rng(7)
    shared = list(rng.integers(4, cfg.vocab_size, 16))
    prompts = {0: shared + list(rng.integers(4, cfg.vocab_size, 5)),
               1: shared + list(rng.integers(4, cfg.vocab_size, 3))}
    dec = _mk(cfg, params)
    marks = []

    def drive():
        dec.ensure_tokens(0, prompts[0])
        dec.step([0])
        marks.append(dec.prefill_tokens_total)
        dec.ensure_tokens(1, prompts[1])
        dec.step([0, 1])
        marks.append(dec.prefill_tokens_total)
        for _ in range(10):
            dec.step([0, 1])

    spans = traced(tmp_path, drive)
    names = {n for n, *_ in spans}
    assert names == {"repro.decoder.grow", "repro.decoder.pages",
                     "repro.decoder.table_sync", "repro.decoder.launch",
                     "repro.decoder.fetch", "repro.decoder.sample"}
    runs = launches(spans)
    assert [r["program"] for r in runs] == (
        ["prefill_into_pages", "decode_step", "prefill_into_pages"]
        + ["decode_step"] * 10)
    # row 0 admits alone (capacity 1) in 3 page-wide chunk rows; row 1
    # grows the pool to 2, row 0 decodes, and row 1 maps the 16 shared
    # tokens and prefills its 3-token tail in one chunk row
    assert runs[0] == dict(runs[0], rows=1, chunks=3, padded_rows=3,
                           tokens=21, padded_tokens=24, new_shape=1,
                           pages_in_use=3, pages_reserved=3)
    assert runs[2] == dict(runs[2], rows=1, chunks=1, padded_rows=1,
                           tokens=3, padded_tokens=8, new_shape=1,
                           pages_reserved=6)
    assert [r["tokens"] for r in runs if r["program"] != "decode_step"] \
        == [marks[0], marks[1] - marks[0]]
    decodes = [r for r in runs if r["program"] == "decode_step"]
    # the pool grew before its first decode: one decode shape, capacity 2
    assert [r["new_shape"] for r in decodes] == [1] + [0] * 10
    assert [r["rows"] for r in decodes] == [1] + [2] * 10
    assert all(r["padded_rows"] == r["padded_tokens"] for r in decodes)
    assert all("chunks" not in r for r in decodes)
    assert all(0 < r["pages_in_use"] <= r["pages_reserved"] for r in runs)
    cow = sum(st.get("cow", 0) for n, _s, _e, st in spans
              if n == "repro.decoder.pages")
    # the first tenant to wrap into a shared page copies it; the other
    # then holds the original alone and writes in place
    assert cow == 1


def test_unpaged_launches_name_their_program(setup, tmp_path):
    """The full-forward reference launches ``forward`` and holds no
    pages."""
    cfg, params = setup
    dec = _mk(cfg, params, slot_cached=False)

    def drive():
        dec.ensure_tokens(0, list(range(4, 14)))
        dec.step([0])
        dec.step([0])

    runs = launches(traced(tmp_path, drive))
    assert runs[0]["program"] == "forward"
    assert runs[0]["tokens"] == 10 and runs[0]["new_shape"] == 1
    assert all("pages_in_use" not in r and "chunks" not in r for r in runs)


@pytest.mark.parametrize("arch", ["smollm2-1.7b", "granite-3-8b"])
def test_decode_launches_name_the_kernel_shape(tmp_path, arch):
    """A decode launch says which paged-kernel body ran: its KV heads,
    q-heads per KV head and head dim; an admission carries none of them."""
    cfg = get_smoke_config(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dec = _mk(cfg, params)

    def drive():
        dec.ensure_tokens(0, list(range(4, 14)))
        dec.step([0])
        dec.step([0])

    runs = launches(traced(tmp_path, drive))
    assert [r["program"] for r in runs] == ["prefill_into_pages",
                                            "decode_step"]
    assert runs[1] == dict(runs[1], kv_heads=cfg.n_kv_heads,
                           q_per_kv=cfg.n_heads // cfg.n_kv_heads,
                           head_dim=cfg.resolved_head_dim)
    assert "kv_heads" not in runs[0]
    assert (runs[1]["kv_heads"], runs[1]["q_per_kv"]) == \
        {"smollm2-1.7b": (4, 1), "granite-3-8b": (2, 2)}[arch]

"""The serving entry points: device description, compile-cache placement,
the smoke preset through ``serve.serve``, and ``chip_smoke.py``'s checks."""
import os
import subprocess
import sys
import types

import jax
import pytest

import chip_smoke
from repro.cluster import TPU_CATALOG, GPU_CATALOG, local_device_model
from repro.launch import serve

ROOT = os.path.join(os.path.dirname(__file__), "..")


class TestLocalDeviceModel:
    @pytest.mark.parametrize("kind,entry", [("TPU v5 lite", "TPU v5e"),
                                            ("TPU v6 lite", "TPU v6e")])
    def test_device_kind_maps_to_catalog(self, monkeypatch, kind, entry):
        monkeypatch.setattr(jax, "devices", lambda: [
            types.SimpleNamespace(device_kind=kind)])
        assert local_device_model() is TPU_CATALOG[entry]

    def test_unknown_kind_is_an_error(self, monkeypatch):
        monkeypatch.setattr(jax, "devices", lambda: [
            types.SimpleNamespace(device_kind="cpu")])
        with pytest.raises(ValueError, match="no catalog entry"):
            local_device_model()

    def test_explicit_name(self):
        assert local_device_model("NVIDIA A10") is GPU_CATALOG["NVIDIA A10"]
        with pytest.raises(KeyError):
            local_device_model("NVIDIA A11")


class TestCompileCache:
    def test_env_dir_is_left_to_jax(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        before = jax.config.jax_compilation_cache_dir
        assert serve.configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_checkout_dir(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            path = serve.configure_compile_cache()
            assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_smoke_serve_passes_chip_smoke_checks(capsys):
    """The served path at the smoke preset: every request done with
    in-vocab tokens, token-exact against the full-forward path."""
    args = serve.parse_args(["--smoke", "--device", "NVIDIA A10",
                             "--claims", "8", "--workers", "2"])
    run = serve.serve(args)
    serve.report(run)
    assert run.cfg.n_layers == 2
    chip_smoke.check_served(run, 8)
    chip_smoke.check_reference(run, 3)
    assert "served == full forward" in capsys.readouterr().out


def test_serve_defaults_to_published_widths():
    args = serve.parse_args([])
    assert not args.smoke and args.arch == "smollm2-1.7b"


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert '"ok"' not in out.stdout

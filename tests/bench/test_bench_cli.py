"""The benchmark's command: no TPU means no result and a non-zero exit."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run_cli(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_run_exits_non_zero_without_a_result():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        p = run_cli("--workload", cell["name"], "--seed", "4000000007",
                    "--seconds", "1", "--trace", "0")
        assert p.returncode == 3, p.stderr[-2000:]
        assert "needs a TPU" in p.stderr
        assert not [ln for ln in p.stdout.splitlines()
                    if ln.startswith("{")]


def test_unknown_workload_fails():
    p = run_cli("--workload", "no-such-cell", "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_files_alone_fail(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own files
    (no program) exits non-zero and prints no result."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for p in bench["paths"]:
        src = ROOT / p
        for f in src.rglob("*"):
            if f.is_file() and "__pycache__" not in f.parts:
                dst = tmp_path / f.relative_to(ROOT)
                dst.parent.mkdir(parents=True, exist_ok=True)
                dst.write_bytes(f.read_bytes())
    cell = bench["workloads"][0]["name"]
    p = run_cli("--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0", cwd=tmp_path)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]

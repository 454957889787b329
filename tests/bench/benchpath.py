"""Puts the benchmark's own directory on the import path of its tests."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

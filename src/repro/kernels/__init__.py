"""Pallas TPU kernels for the compute hot-spots, each with:

- ``<name>.py``  — the ``pl.pallas_call`` kernel with explicit BlockSpec VMEM
  tiling (TPU is the target; validated via ``interpret=True`` on CPU),
- ``ops.py``     — wrapper that stages the kernel for programs compiled for
  TPU and the reference for every other platform (``platform.py``; the
  CPU dry-run therefore lowers the pure-XLA reference path),
- ``ref.py``     — pure-jnp oracle used by the allclose test sweeps.

Kernels: flash_attention (prefill/train), decode_attention (single-token GQA
attention against a ring KV cache), ssm_scan (selective-SSM chunked scan).
"""
from . import flash_attention, decode_attention, ssm_scan  # noqa: F401

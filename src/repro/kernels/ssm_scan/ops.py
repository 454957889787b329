"""Selective-SSM scan: the Pallas kernel in a program compiled for TPU, the
pure-jnp reference elsewhere (see ``kernels/platform.py``)."""
from __future__ import annotations

import functools

from ..platform import tpu_kernel_else_ref
from .ref import ssm_scan_ref, ssm_step_ref
from .ssm_scan import ssm_scan_pallas


def ssm_scan(x, dt, A, B, C, D, *, chunk: int = 128):
    """Shapes as in ref.py."""
    return tpu_kernel_else_ref(functools.partial(ssm_scan_pallas, chunk=chunk),
                               ssm_scan_ref, x, dt, A, B, C, D)


ssm_step = ssm_step_ref  # single-token decode step (pure jnp everywhere)

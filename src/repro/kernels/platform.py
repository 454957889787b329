"""Kernel-or-reference choice by the platform a program is compiled for.

``jax.lax.platform_dependent`` stages both callables out and keeps only the
one for the platform the enclosing program is lowered for: a program
compiled for a TPU always contains the Pallas kernel (a ``tpu_custom_call``),
whatever device the compiling process has, and any other platform lowers
the pure-jnp reference.  Nothing falls back at run time: a shape the kernel
cannot tile is a TPU compile error, never a silent switch to the reference.

Gradients always come from the reference (the kernels have no backward),
so the training path differentiates on every platform.
"""
from __future__ import annotations

import jax


def tpu_kernel_else_ref(kernel, ref, *args):
    """``kernel(*args)`` in a program compiled for TPU, else ``ref(*args)``.

    Both take the same positional array arguments and return the same
    pytree of arrays; differentiation goes through ``ref``."""
    @jax.custom_vjp
    def run(*a):
        return jax.lax.platform_dependent(*a, tpu=kernel, default=ref)

    def fwd(*a):
        return run(*a), a

    def bwd(a, g):
        return jax.vjp(ref, *a)[1](g)

    run.defvjp(fwd, bwd)
    return run(*args)

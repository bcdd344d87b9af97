"""Jitted grouped-matmul wrapper; ``interpret=True`` (CPU tests) is explicit."""
from __future__ import annotations

import functools

import jax

from repro.kernels.grouped_matmul.kernel import grouped_matmul_kernel


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d",
                                             "interpret"))
def grouped_matmul(x, w, *, block_c=128, block_f=128, block_d=256,
                   interpret=False):
    return grouped_matmul_kernel(x, w, block_c=block_c, block_f=block_f,
                                 block_d=block_d, interpret=interpret)

"""Jitted public wrapper: (B, S, H, D) model layout -> kernel layout, GQA
expansion. ``interpret=True`` runs the kernel body in the Pallas
interpreter (CPU tests); the caller says so, nothing infers it."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_kernel


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=128, block_kv=128, interpret=False):
    """q: (B, S, H, D); k/v: (B, S, KV, D). Returns (B, S, H, D)."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    if kv != h:
        g = h // kv
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = flash_attention_kernel(qt, kt, vt, causal=causal, window=window,
                                 softcap=softcap, block_q=block_q,
                                 block_kv=block_kv, interpret=interpret)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)

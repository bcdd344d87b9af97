"""Jitted SSD wrapper: Pallas intra-chunk kernel + host inter-chunk scan.
Same contract as repro.models.mamba2.ssd_chunked."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_intra_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_pallas(x, dt, A, B, C, chunk: int, interpret=False):
    """x: (b,s,nh,hd); dt: (b,s,nh); A: (nh,); B/C: (b,s,ds).
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU)."""
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    assert s % chunk == 0
    nc = s // chunk
    f32 = jnp.float32

    dtc = dt.reshape(b, nc, chunk, nh).astype(f32)
    a = (dtc * A).transpose(0, 3, 1, 2)                      # (b,nh,nc,c)
    xc = x.reshape(b, nc, chunk, nh, hd)
    xdt = (xc.astype(f32) * dtc[..., None]).transpose(0, 3, 1, 2, 4)
    Bc = B.reshape(b, nc, chunk, ds)
    Cc = C.reshape(b, nc, chunk, ds)

    acs = jnp.cumsum(a, axis=-1)                             # (b,nh,nc,c)
    y_intra, s_loc = ssd_intra_chunk(acs, xdt, Bc, Cc, interpret=interpret)

    # inter-chunk recurrence (cheap): S_n = dec_n * S_{n-1} + S_n_local
    chunk_decay = jnp.exp(acs[..., -1])                      # (b,nh,nc)
    s0 = jnp.zeros((b, nh, ds, hd), f32)

    def step(state, inp):
        dec, sl = inp                                        # (b,nh),(b,nh,ds,hd)
        prev = state
        return state * dec[..., None, None] + sl, prev

    final, s_prev = jax.lax.scan(
        step, s0, (chunk_decay.transpose(2, 0, 1),
                   s_loc.transpose(2, 0, 1, 3, 4)))
    s_prev = s_prev.transpose(1, 2, 0, 3, 4)                 # (b,nh,nc,ds,hd)

    y_inter = jnp.einsum("bncs,bhnsp->bhncp", Cc.astype(f32), s_prev) \
        * jnp.exp(acs)[..., None]
    y = (y_intra.astype(f32) + y_inter)                      # (b,nh,nc,c,hd)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, s, nh, hd).astype(x.dtype)
    # final state in models' (b, nh, hd, ds) layout
    return y, final.transpose(0, 1, 3, 2)

"""SSD (state-space duality) intra-chunk TPU kernel.

Mamba2's chunked algorithm splits into (a) an O(c^2) *intra-chunk dual form*
— two (c x c) matmuls per (batch, head, chunk) that dominate compute — and
(b) a cheap inter-chunk state recurrence. This kernel computes (a) plus the
per-chunk outgoing state entirely in VMEM:

  L        = exp(segsum(a))  (lower-tri decay, (c, c))
  y_intra  = ((C B^T) * L) @ (dt * x)
  S_local  = (B * exp(a_end - a_cs) * dt)^T @ x        ((ds, hd))

Grid: (batch, heads, chunks); B/C blocks are shared across the head grid
dim (their index maps ignore it). The host-side lax.scan carries the state
recurrence and adds the C @ S_prev read-back term (cheap, O(c·ds·hd)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(acs_row_ref, acs_col_ref, xdt_ref, b_ref, c_ref, y_ref,
                state_ref, *, chunk):
    # the within-chunk cumulative log-decay arrives precomputed, once as a
    # row (1, c) and once as a column (c, 1): Mosaic has no cumsum, and the
    # two layouts give the (c, c) decay matrix by broadcasting alone
    acs_row = acs_row_ref[0, 0, 0]               # (1, c) f32
    acs_col = acs_col_ref[0, 0, 0]               # (c, 1) f32
    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)   # (c, hd)   (dt*x)
    bmat = b_ref[0, 0].astype(jnp.float32)       # (c, ds)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (c, ds)
    # L[i, j] = exp(acs_i - acs_j) for i >= j
    diff = acs_col - acs_row                     # (c, c)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    decay_out = jnp.exp(acs_col[chunk - 1:, :] - acs_col)     # (c, 1)
    bw = bmat * decay_out                        # (c, ds)
    state = jax.lax.dot_general(bw, xdt, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state_ref[0, 0, 0] = state                   # (ds, hd)


def ssd_intra_chunk(acs, xdt, B, C, *, interpret=False):
    """acs: (b, nh, nc, c) f32 within-chunk cumulative log-decays;
    xdt: (b, nh, nc, c, hd); B/C: (b, nc, c, ds). Returns (y_intra
    (b,nh,nc,c,hd), S_local (b,nh,nc,ds,hd))."""
    b, nh, nc, c = acs.shape
    hd = xdt.shape[-1]
    ds = B.shape[-1]
    acs = acs.astype(jnp.float32)
    kernel = functools.partial(_ssd_kernel, chunk=c)
    y, state = pl.pallas_call(
        kernel,
        grid=(b, nh, nc),
        in_specs=[
            # trailing (1, c) / (c, 1) blocks span whole array dims, which
            # is what the (8, 128) tiling rule asks of a block this narrow
            pl.BlockSpec((1, 1, 1, 1, c), lambda i, j, n: (i, j, n, 0, 0)),
            pl.BlockSpec((1, 1, 1, c, 1), lambda i, j, n: (i, j, n, 0, 0)),
            pl.BlockSpec((1, 1, 1, c, hd), lambda i, j, n: (i, j, n, 0, 0)),
            pl.BlockSpec((1, 1, c, ds), lambda i, j, n: (i, n, 0, 0)),
            pl.BlockSpec((1, 1, c, ds), lambda i, j, n: (i, n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, c, hd), lambda i, j, n: (i, j, n, 0, 0)),
            pl.BlockSpec((1, 1, 1, ds, hd), lambda i, j, n: (i, j, n, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, nc, c, hd), xdt.dtype),
            jax.ShapeDtypeStruct((b, nh, nc, ds, hd), jnp.float32),
        ],
        interpret=interpret,
    )(acs[:, :, :, None, :], acs[..., None], xdt, B, C)
    return y, state

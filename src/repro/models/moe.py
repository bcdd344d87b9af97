"""Mixture-of-Experts layer with explicit expert parallelism.

Design (GShard-style capacity, megablocks-style grouped compute):
  * router/top-k runs replicated over the ``model`` axis (activations are
    batch-sharded only), so every TP rank sees identical assignments;
  * experts are sharded over ``model`` (EP); each rank owns E/|model| experts
    and builds fixed-capacity buffers for them via rank-ordered scatter
    (static shapes, drop-on-overflow);
  * expert FFN is one batched einsum over the rank's expert buffers;
  * partial outputs are combined with a single ``psum`` over ``model``.

Collectives per MoE layer: all-gather of expert weights over the FSDP axes
(ZeRO-3) + one psum over ``model``. No all-to-all is needed because
activations are replicated across ``model`` (they are sharded across
``data``/``pod``); this is the TPU-native mapping of the paper's
"short-lived service dispatch" — work units are routed to the service
replica (expert shard) that owns them.

``moe_apply_ref`` is the dense oracle used by tests (dropless).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, mlp_init, mlp_apply


def moe_init(key, cfg, dtype):
    m = cfg.moe
    d, f, e = cfg.d_model, m.expert_d_ff, m.num_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "wi": dense_init(ks[1], (e, d, f), dtype),
        "wg": dense_init(ks[2], (e, d, f), dtype),
        "wo": dense_init(ks[3], (e, f, d), dtype, scale=1.0 / math.sqrt(f)),
    }
    ax = {
        "router": ("embed", "experts"),
        "wi": ("experts", "embed", "expert_mlp"),
        "wg": ("experts", "embed", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "embed"),
    }
    if m.shared_expert_d_ff:
        sp, sax = mlp_init(ks[4], d, m.shared_expert_d_ff, dtype)
        p["shared"] = sp
        ax["shared"] = {k: ("embed", "mlp") if k != "wo" else ("mlp", "embed")
                        for k in sax}
    return p, ax


def _route(router_w, x_flat, top_k: int):
    """x_flat: (T, d). Returns top-k weights/idx and Switch aux loss terms."""
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    topk_w, topk_idx = jax.lax.top_k(probs, top_k)             # (T, k)
    topk_w = topk_w / jnp.maximum(topk_w.sum(-1, keepdims=True), 1e-9)
    e = router_w.shape[1]
    # load-balance aux: E * sum_e f_e * P_e
    assign = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32).sum(1)  # (T, E)
    f_e = assign.mean(0) / top_k
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e)
    return topk_w, topk_idx, aux


def _capacity(tokens: int, top_k: int, num_experts: int, factor: float) -> int:
    return max(1, int(math.ceil(tokens * top_k / num_experts * factor)))


def _expert_buffers(x_flat, topk_w, topk_idx, expert_ids, capacity: int):
    """Fixed-capacity buffers for a set of experts.

    Returns (buf_x (E_loc,C,d), buf_w (E_loc,C), tok_of_slot (E_loc,C) int32,
    valid (E_loc,C)). Rank-ordered scatter: assignment j for expert e lands in
    slot ``rank_j`` (its order among e's assignments) if rank_j < C.
    """
    t, k = topk_idx.shape
    a = topk_idx.reshape(-1)                       # (T*k,)
    w = topk_w.reshape(-1)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    bufs_w, bufs_tok, bufs_valid = [], [], []
    for e in expert_ids:
        mask = a == e
        rank = jnp.cumsum(mask) - 1                # order among e's tokens
        keep = mask & (rank < capacity)
        slot = jnp.where(keep, rank, capacity)     # overflow -> spill row
        z = jnp.zeros((capacity + 1,), jnp.float32)
        bufs_w.append(z.at[slot].add(jnp.where(keep, w, 0.0))[:capacity])
        zt = jnp.zeros((capacity + 1,), jnp.int32)
        bufs_tok.append(zt.at[slot].add(jnp.where(keep, tok, 0))[:capacity])
        bufs_valid.append(z.at[slot].add(keep.astype(jnp.float32))[:capacity])
    buf_w = jnp.stack(bufs_w)                      # (E_loc, C)
    buf_tok = jnp.stack(bufs_tok)
    valid = jnp.stack(bufs_valid)
    buf_x = x_flat[buf_tok] * valid[..., None].astype(x_flat.dtype)
    return buf_x, buf_w, buf_tok, valid


def _expert_ffn(wi, wg, wo, buf_x):
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf_x, wg)) * jnp.einsum(
        "ecd,edf->ecf", buf_x, wi)
    return jnp.einsum("ecf,efd->ecd", h, wo)


def moe_apply(params, cfg, x, mesh, parallel, capacity_factor=None):
    """x: (B, S, d) batch-sharded. Returns (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    tp_axis = parallel.tp_axis if parallel is not None else None
    tp = mesh.shape[tp_axis] if (tp_axis and mesh is not None) else 1
    if tp == 1 or m.num_experts % tp != 0:
        # single-rank fallback (tests / tiny meshes without model axis)
        y, aux = _moe_local(params, cfg, x, cf)
        return _maybe_shared(params, x, y), aux

    e_loc = m.num_experts // tp
    bspec = P(parallel.batch_axes, None, None)
    wspec = P(tp_axis, parallel.fsdp_axes, None)

    def f(x_blk, router_w, wi, wg, wo):
        # x_blk: (B_loc, S, d) full d; wi/wg/wo: (E_loc, d/|fsdp|, f)
        if parallel.fsdp_axes:
            wi = _allgather(wi, parallel.fsdp_axes, axis=1)
            wg = _allgather(wg, parallel.fsdp_axes, axis=1)
            wo = _allgather(wo, parallel.fsdp_axes, axis=1)
        bl, sl, _ = x_blk.shape
        xf = x_blk.reshape(bl * sl, d)
        topk_w, topk_idx, aux = _route(router_w, xf, m.top_k)
        cap = _capacity(bl * sl, m.top_k, m.num_experts, cf)
        rank = jax.lax.axis_index(tp_axis)
        first = rank * e_loc
        # build buffers for this rank's experts (python loop over local ids
        # with traced offset): expert id = first + i
        t, k = topk_idx.shape
        a = topk_idx.reshape(-1)
        w = topk_w.reshape(-1)
        tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
        bw, bt, bv = [], [], []
        for i in range(e_loc):
            mask = a == (first + i)
            rnk = jnp.cumsum(mask) - 1
            keep = mask & (rnk < cap)
            slot = jnp.where(keep, rnk, cap)
            z = jnp.zeros((cap + 1,), jnp.float32)
            bw.append(z.at[slot].add(jnp.where(keep, w, 0.0))[:cap])
            zt = jnp.zeros((cap + 1,), jnp.int32)
            bt.append(zt.at[slot].add(jnp.where(keep, tok, 0))[:cap])
            bv.append(z.at[slot].add(keep.astype(jnp.float32))[:cap])
        buf_w = jnp.stack(bw); buf_tok = jnp.stack(bt); valid = jnp.stack(bv)
        buf_x = xf[buf_tok] * valid[..., None].astype(xf.dtype)
        h = _expert_ffn(wi, wg, wo, buf_x)         # (E_loc, C, d)
        gate = (buf_w * valid).astype(h.dtype)[..., None]
        y = jnp.zeros_like(xf).at[buf_tok.reshape(-1)].add(
            (h * gate).reshape(-1, d))
        y = jax.lax.psum(y, tp_axis)
        aux = jax.lax.pmean(aux, parallel.batch_axes) if parallel.batch_axes else aux
        return y.reshape(bl, sl, d), aux

    y, aux = jax.shard_map(
        f, mesh=mesh,
        in_specs=(bspec, P(), wspec, wspec, wspec),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, params["router"], params["wi"], params["wg"], params["wo"])
    return _maybe_shared(params, x, y), aux


def _allgather(w, axes, axis: int):
    for ax in reversed(axes):
        w = jax.lax.all_gather(w, ax, axis=axis, tiled=True)
    return w


def _maybe_shared(params, x, y):
    if "shared" in params:
        y = y + mlp_apply(params["shared"], x)
    return y


def _moe_local(params, cfg, x, cf):
    """Single-rank capacity MoE (same math as the EP path, no collectives)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    topk_w, topk_idx, aux = _route(params["router"], xf, m.top_k)
    cap = _capacity(b * s, m.top_k, m.num_experts, cf)
    buf_x, buf_w, buf_tok, valid = _expert_buffers(
        xf, topk_w, topk_idx, range(m.num_experts), cap)
    h = _expert_ffn(params["wi"], params["wg"], params["wo"], buf_x)
    gate = (buf_w * valid).astype(h.dtype)[..., None]
    y = jnp.zeros_like(xf).at[buf_tok.reshape(-1)].add((h * gate).reshape(-1, d))
    return y.reshape(b, s, d), aux


def moe_apply_ref(params, cfg, x):
    """Dense dropless oracle: y = sum_k w_k * ffn_{idx_k}(x). O(T*E*d*f)."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    topk_w, topk_idx, aux = _route(params["router"], xf, m.top_k)
    y = jnp.zeros_like(xf)
    for e in range(m.num_experts):
        h = jax.nn.silu(xf @ params["wg"][e]) * (xf @ params["wi"][e])
        fe = h @ params["wo"][e]
        w_e = jnp.where(topk_idx == e, topk_w, 0.0).sum(-1)    # (T,)
        y = y + fe * w_e[:, None].astype(fe.dtype)
    return _maybe_shared(params, x, y.reshape(b, s, d)), aux

"""Model assembly for all 10 assigned architectures.

One uniform interface per architecture family:

    model = build_model(cfg, mesh=None, parallel=None)
    params, axes = model.init(key)
    logits, aux  = model.forward(params, inputs)            # train path
    logits, cache = model.prefill(params, inputs)           # inference prefill
    logits, cache = model.decode(params, cache, inputs, pos)
    cache, cache_axes = model.init_cache(batch, max_seq)

``inputs`` is token ids (B, S) int32, or precomputed embeddings (B, S, d)
for the stub-frontend archs (musicgen/internvl2, ``input_mode="embeddings"``).

Layer stacks are built as *super-blocks* scanned with ``lax.scan`` (params
stacked on a leading axis), so HLO size is depth-independent:
  gemma2   : 23 x (local, global)
  gemma3   : 8  x (5 local + 1 global)
  llama4   : 24 x (dense-FFN layer, MoE layer)
  granite  : 24 x (MoE layer)
  qwen/yi/musicgen/internvl: L x (global)
  mamba2   : 48 x (mamba)
  zamba2   : 6 segments x 6 mamba + shared attn application, + 2 trailing

Local (sliding-window) layers use rolling KV caches of size ``window`` in
decode (gemma3 decode_32k: 5/6 of layers hold a 1k cache instead of 32k).
"""
from __future__ import annotations

import dataclasses
import functools
from types import SimpleNamespace
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE


# ---------------------------------------------------------------------------
# Sub-block descriptors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sub:
    window: int          # 0 = global attention
    theta: float
    ffn: str             # "dense" | "moe"


def program(cfg: ModelConfig):
    """Returns (n_super, [Sub, ...]) for attention-family archs."""
    theta_g = cfg.rope_theta_global or cfg.rope_theta
    if cfg.local_global_pattern:
        lp, gp = cfg.local_global_pattern
        subs = [Sub(cfg.sliding_window, cfg.rope_theta, "dense")] * lp + \
               [Sub(0, theta_g, "dense")] * gp
        assert cfg.num_layers % (lp + gp) == 0
        return cfg.num_layers // (lp + gp), subs
    if cfg.family == "moe":
        n = cfg.moe.moe_every_n
        subs = [Sub(0, theta_g, "dense")] * (n - 1) + [Sub(0, theta_g, "moe")]
        assert cfg.num_layers % n == 0
        return cfg.num_layers // n, subs
    return cfg.num_layers, [Sub(0, theta_g, "dense")]


# ---------------------------------------------------------------------------
# Attention/FFN sub-layer (shared by dense, moe, and zamba's shared block)
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _diff_barrier(h):
    """``optimization_barrier`` with a differentiation rule: the installed JAX
    has no AD rule for the primitive, so the train path (19 seed failures)
    could not backprop through the scan body. The barrier is kept in both the
    forward and transposed loops — its whole point is stopping XLA from
    hoisting the f32 convert of the saved-h stack out of the (transposed)
    loop — and the vjp makes it transparent to AD."""
    return jax.lax.optimization_barrier(h)


def _diff_barrier_fwd(h):
    return jax.lax.optimization_barrier(h), None


def _diff_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_diff_barrier.defvjp(_diff_barrier_fwd, _diff_barrier_bwd)


def sub_init(key, cfg: ModelConfig, sub: Sub, dtype, h_pad=None):
    k1, k2, k3 = jax.random.split(key, 3)
    attn_p, attn_ax = L.attn_init(k1, cfg, dtype, h_pad=h_pad)
    p = {"ln1": jnp.zeros((cfg.d_model,), dtype), "attn": attn_p,
         "ln2": jnp.zeros((cfg.d_model,), dtype)}
    ax = {"ln1": ("norm",), "attn": attn_ax, "ln2": ("norm",)}
    if sub.ffn == "dense":
        p["mlp"], ax["mlp"] = L.mlp_init(k2, cfg.d_model, cfg.d_ff, dtype)
    else:
        p["moe"], ax["moe"] = MOE.moe_init(k2, cfg, dtype)
    if cfg.post_norm:
        p["post_ln1"] = jnp.zeros((cfg.d_model,), dtype)
        p["post_ln2"] = jnp.zeros((cfg.d_model,), dtype)
        ax["post_ln1"] = ("norm",)
        ax["post_ln2"] = ("norm",)
    return p, ax


def _ones_like_tree(tree):
    return jax.tree.map(lambda _: 1.0, tree)


def sub_masks(cfg: ModelConfig, sub: Sub, params, h_pad=None):
    """Grad-mask tree with the same structure as sub_init params."""
    m = {"ln1": 1.0, "attn": L.attn_grad_masks(cfg, h_pad), "ln2": 1.0}
    if sub.ffn == "dense":
        m["mlp"] = _ones_like_tree(params["mlp"])
    else:
        m["moe"] = _ones_like_tree(params["moe"])
    if cfg.post_norm:
        m["post_ln1"] = 1.0
        m["post_ln2"] = 1.0
    return m


def _rolling(cfg, sub: Sub, max_seq: int) -> bool:
    return bool(sub.window) and sub.window < max_seq


def _cache_len(cfg, sub: Sub, max_seq: int) -> int:
    return min(sub.window, max_seq) if _rolling(cfg, sub, max_seq) else max_seq


def _build_prefill_cache(k, v, cache_len: int):
    """k/v: (B, S, KV, hd) -> cache of length cache_len (rolling if < S)."""
    b, s, kvh, hd = k.shape
    if cache_len >= s:
        pad = cache_len - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return kc, vc
    w = cache_len
    pos = jnp.arange(s - w, s)
    slots = pos % w
    kc = jnp.zeros((b, w, kvh, hd), k.dtype).at[:, slots].set(k[:, s - w:])
    vc = jnp.zeros((b, w, kvh, hd), v.dtype).at[:, slots].set(v[:, s - w:])
    return kc, vc


def _decode_attn_rolling(cfg, q, k_cache, v_cache, pos, window: int):
    """Rolling-cache decode attention. Slot s holds absolute position
    pos - ((pos - s) mod W); valid iff >= 0."""
    b = q.shape[0]
    w = k_cache.shape[1]
    slots = jnp.arange(w)
    kpos = pos[:, None] - jnp.mod(pos[:, None] - slots[None, :], w)
    valid = kpos >= 0
    kvh = k_cache.shape[2]
    qg = L._group(q, kvh)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k_cache).astype(jnp.float32)
    s = L.softcap(s * (1.0 / (cfg.head_dim ** 0.5)), cfg.attn_softcap)
    s = jnp.where(valid[:, None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", p, v_cache)
    return out.reshape(b, 1, cfg.num_heads, cfg.head_dim)


def sub_apply(p, cfg: ModelConfig, sub: Sub, h, positions, mode: str,
              cache=None, pos=None, max_seq: Optional[int] = None,
              mesh=None, parallel=None, expand=False, policy=None):
    """One transformer sub-layer. Returns (h, aux, new_cache)."""
    hn = L.rms_norm(h, p["ln1"], cfg.norm_eps)
    q, k, v = L.qkv_proj(p["attn"], cfg, hn, positions, sub.theta)
    new_cache = None
    if mode == "decode":
        b = h.shape[0]
        w = cache["k"].shape[1]
        rolling = _rolling(cfg, sub, max_seq)
        slot = (pos % w) if rolling else pos
        kc = cache["k"].at[jnp.arange(b), slot].set(k[:, 0])
        vc = cache["v"].at[jnp.arange(b), slot].set(v[:, 0])
        if rolling:
            attn = _decode_attn_rolling(cfg, q, kc, vc, pos, sub.window)
        else:
            attn = L.decode_attention(cfg, q, kc, vc, pos, window=sub.window)
        new_cache = {"k": kc, "v": vc}
    elif mode == "chunk":
        # chunked prefill: write the chunk's K/V at its absolute positions
        # into the full-length cache and attend over the cache (global
        # attention only — the serving engine gates chunking on padding
        # safety, so rolling/SSM/MoE sub-layers never see this mode)
        b = h.shape[0]
        kc = cache["k"].at[jnp.arange(b)[:, None], positions].set(k)
        vc = cache["v"].at[jnp.arange(b)[:, None], positions].set(v)
        attn = L.chunk_attention(cfg, q, kc, vc, positions)
        new_cache = {"k": kc, "v": vc}
    else:
        if mode == "prefill":
            kc, vc = _build_prefill_cache(k, v, _cache_len(cfg, sub, max_seq))
            new_cache = {"k": kc, "v": vc}
        if expand:
            h_pad = q.shape[2]
            head_map = L.kv_head_map(cfg.num_heads, cfg.num_kv_heads, h_pad)
            k = L.expand_kv(k, head_map)
            v = L.expand_kv(v, head_map)
            if policy is not None:
                k = policy.constraint(k, ("batch", "seq", "q_heads", "head_dim"))
                v = policy.constraint(v, ("batch", "seq", "q_heads", "head_dim"))
        core = lambda q_, k_, v_: L.attention(cfg, q_, k_, v_,
                                              window=sub.window)
        if mode == "train":
            # flash-backward semantics: save only (q, k, v) and recompute
            # the f32 score/prob buffers in the bwd pass — they are
            # O(S x block) per head and would otherwise dominate live HBM
            # (the Pallas kernel keeps them in VMEM on TPU).
            core = jax.checkpoint(core, prevent_cse=False)
        attn = core(q, k, v)
    out = jnp.einsum("bshk,hkd->bsd", attn, p["attn"]["wo"])
    if cfg.post_norm:
        out = L.rms_norm(out, p["post_ln1"], cfg.norm_eps)
    h = h + out
    hn = L.rms_norm(h, p["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if sub.ffn == "dense":
        mo = L.mlp_apply(p["mlp"], hn)
    else:
        mo, aux = MOE.moe_apply(p["moe"], cfg, hn, mesh, parallel)
    if cfg.post_norm:
        mo = L.rms_norm(mo, p["post_ln2"], cfg.norm_eps)
    return h + mo, aux, new_cache


def init_sub_cache(cfg, sub: Sub, batch: int, max_seq: int, dtype):
    w = _cache_len(cfg, sub, max_seq)
    c = {"k": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype),
         "v": jnp.zeros((batch, w, cfg.num_kv_heads, cfg.head_dim), dtype)}
    ax = {"k": ("batch", "seq_kv", "kv_heads", "head_dim"),
          "v": ("batch", "seq_kv", "kv_heads", "head_dim")}
    return c, ax


# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------


def _remat(fn, policy_name: str):
    # prevent_cse=False is the scan-safe form (True inserts optimization
    # barriers that make XLA materialize f32 cotangent stacks per layer).
    if policy_name == "none":
        return fn
    if policy_name == "minimal":
        return jax.checkpoint(
            fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn, prevent_cse=False)  # "full": recompute block


# ---------------------------------------------------------------------------
# Model builders
# ---------------------------------------------------------------------------


def init_params(model, key, device=None):
    """Params from one jitted ``model.init``, written straight to ``device``
    (the default device when None). Eager init would materialize each
    stacked weight in f32 before its bf16 cast: at published widths one
    such transient is gigabytes."""
    sharding = (jax.sharding.SingleDeviceSharding(device)
                if device is not None else None)
    return jax.jit(lambda k: model.init(k)[0], out_shardings=sharding)(key)


def _stacked_init(key, n: int, one_init):
    keys = jax.random.split(key, n)
    return jax.vmap(one_init)(keys)


def _stack_axes(ax_tree):
    return jax.tree.map(lambda a: ("super",) + a, ax_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))




def _constrainer(policy):
    """Returns (constrain_h, constrain_logits) given an optional ShardingPolicy."""
    if policy is None:
        return lambda h: h, lambda lg: lg

    def ch(h):
        return policy.constraint(h, ("batch",) + ("seq",) * (h.ndim - 2) + ("act",))

    def cl(lg):
        return policy.constraint(lg, ("batch", "seq", "vocab"))
    return ch, cl

def build_model(cfg: ModelConfig, mesh=None, parallel=None, policy=None):
    if cfg.family in ("dense", "moe"):
        return _build_transformer(cfg, mesh, parallel, policy)
    if cfg.family == "ssm":
        return _build_ssm(cfg, mesh, parallel, policy)
    if cfg.family == "hybrid":
        return _build_hybrid(cfg, mesh, parallel, policy)
    raise ValueError(cfg.family)


def _embed_inputs(cfg, emb_p, inputs):
    if cfg.input_mode == "embeddings":
        return inputs.astype(_dtype(cfg))
    return L.embed_apply(emb_p, inputs, cfg.d_model)


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


def _logits(emb_p, cfg, h):
    return L.unembed_apply(emb_p, cfg, h)


# -- dense / moe transformer -------------------------------------------------


def _build_transformer(cfg, mesh, parallel, policy=None):
    cb = _constrainer(policy)
    n_super, subs = program(cfg)
    dtype = _dtype(cfg)
    expand = policy is not None and policy.mode == "expand"
    h_pad = policy.h_pad if expand else None
    sub_axes = []           # per-sub logical axes WITHOUT the scan dim
    for sub in subs:
        cap = {}

        def _f(key, sub=sub, cap=cap):
            p, ax = sub_init(key, cfg, sub, dtype, h_pad=h_pad)
            cap["ax"] = ax
            return p

        jax.eval_shape(_f, jax.random.PRNGKey(0))
        sub_axes.append(cap["ax"])

    def init(key):
        ke, kf, *ks = jax.random.split(key, 2 + len(subs))
        emb_p, emb_ax = L.embed_init(ke, cfg, dtype)
        blocks, blocks_ax = [], []
        for sub, ax, k in zip(subs, sub_axes, ks):
            def one(kk, sub=sub):
                return sub_init(kk, cfg, sub, dtype, h_pad=h_pad)[0]
            stacked = _stacked_init(k, n_super, one)
            blocks.append(stacked)
            blocks_ax.append(_stack_axes(ax))
        params = {"embed": emb_p, "blocks": blocks,
                  "final_norm": jnp.zeros((cfg.d_model,), dtype)}
        axes = {"embed": emb_ax, "blocks": blocks_ax, "final_norm": ("norm",)}
        return params, axes

    def grad_masks(params):
        if not expand or h_pad == cfg.num_heads:
            return None
        return {
            "embed": _ones_like_tree(params["embed"]),
            "blocks": [sub_masks(cfg, sub, jax.tree.map(lambda x: x[0], bp),
                                 h_pad)
                       for sub, bp in zip(subs, params["blocks"])],
            "final_norm": 1.0,
        }

    def _scan(params, h, positions, mode, caches=None, pos=None,
              max_seq=None, remat=False):
        """Scan over super-blocks. caches: list per sub of stacked cache."""
        def body(carry, xs):
            h, aux = carry
            # barrier: stops XLA from hoisting convert(saved-h-stack) to f32
            # out of the transposed loop (a 2x residual-memory artifact)
            h = _diff_barrier(h)
            block_ps = xs[:len(subs)]
            cache_slices = xs[len(subs):] if mode != "train" and caches else \
                [None] * len(subs)
            new_caches = []
            for sub, ax, bp, cs in zip(subs, sub_axes, block_ps, cache_slices):
                if policy is not None:
                    bp = policy.constrain_tree(bp, ax)
                h, a, nc = sub_apply(
                    bp, cfg, sub, h, positions, mode,
                    cache=cs, pos=pos, max_seq=max_seq,
                    mesh=mesh, parallel=parallel, expand=expand,
                    policy=policy)
                aux = aux + a
                new_caches.append(nc)
            h = cb[0](h)
            ys = tuple(new_caches) if mode != "train" else None
            return (h, aux), ys

        fn = _remat(body, cfg.remat_policy) if remat else body
        xs = tuple(params["blocks"])
        if mode != "train" and caches is not None:
            xs = xs + tuple(caches)
        (h, aux), ys = jax.lax.scan(fn, (h, jnp.zeros((), jnp.float32)), xs)
        return h, aux, ys

    def forward(params, inputs):
        b = inputs.shape[0]
        s = inputs.shape[1]
        positions = jnp.arange(s)[None, :]
        h = _embed_inputs(cfg, params["embed"], inputs)
        h = cb[0](h)
        h, aux, _ = _scan(params, h, positions, "train", remat=True)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return cb[1](_logits(params["embed"], cfg, h)), aux

    def prefill(params, inputs, max_seq: int):
        s = inputs.shape[1]
        positions = jnp.arange(s)[None, :]
        h = _embed_inputs(cfg, params["embed"], inputs)
        h, aux, caches = _scan(params, h, positions, "prefill", max_seq=max_seq)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h[:, -1:]), list(caches)

    def decode(params, caches, inputs, pos):
        positions = pos[:, None]
        h = _embed_inputs(cfg, params["embed"], inputs)
        max_seq = caches[_global_sub_index(subs)]["k"].shape[2]
        h, aux, new_caches = _scan(params, h, positions, "decode",
                                   caches=caches, pos=pos, max_seq=max_seq)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h), list(new_caches)

    def prefill_chunk(params, caches, inputs, pos0):
        """Chunk-wise prefill: run ``inputs`` (B,C) — one chunk of a longer
        prompt starting at absolute positions ``pos0`` (B,) — against the
        full-length ``caches``, writing the chunk's K/V in place. Earlier
        chunks (and any prefix-cache restore) must already occupy positions
        [0, pos0). Exact only for all-global (padding-safe) models; the
        serving engine gates on that."""
        c = inputs.shape[1]
        positions = pos0[:, None] + jnp.arange(c)[None, :]
        h = _embed_inputs(cfg, params["embed"], inputs)
        max_seq = caches[_global_sub_index(subs)]["k"].shape[2]
        h, aux, new_caches = _scan(params, h, positions, "chunk",
                                   caches=caches, pos=pos0, max_seq=max_seq)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h), list(new_caches)

    def decode_verify(params, caches, candidate_tokens, pos):
        """Speculative-decode verify: score ``candidate_tokens`` (B, K+1) —
        the last emitted token followed by K draft proposals — in ONE
        batched call, returning logits for every candidate position. Rides
        the chunk machinery: candidate K/V is written at absolute positions
        ``pos..pos+K`` and chunk attention masks ``kpos <= qpos``, so
        positions past the accepted prefix hold stale K/V that later decode
        steps never attend (their masks stop at the slot's position) and
        overwrite in place — rejection is a per-slot *position* rollback,
        not a cache rollback. Exact only where chunked prefill is (all-
        global attention); the serving engine gates on that, and rolling/
        SSM/hybrid models (no ``decode_verify``) degrade to k=1."""
        return prefill_chunk(params, caches, candidate_tokens, pos)

    def init_cache(batch: int, max_seq: int):
        caches, axes = [], []
        for sub in subs:
            c, ax = init_sub_cache(cfg, sub, batch, max_seq, dtype)
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (n_super,) + x.shape), c))
            axes.append(_stack_axes(ax))
        return caches, axes

    return SimpleNamespace(cfg=cfg, init=init, forward=forward,
                           prefill=prefill, decode=decode,
                           prefill_chunk=prefill_chunk,
                           decode_verify=decode_verify,
                           init_cache=init_cache, n_super=n_super, subs=subs,
                           grad_masks=grad_masks)


def _global_sub_index(subs):
    for i, s in enumerate(subs):
        if s.window == 0:
            return i
    return 0


# -- pure SSM (mamba2) -------------------------------------------------------


def _build_ssm(cfg, mesh, parallel, policy=None):
    cb = _constrainer(policy)
    dtype = _dtype(cfg)
    n = cfg.num_layers
    cap = {}

    def _one_abs(kk):
        mp, max_ = M.mamba_init(kk, cfg, dtype)
        cap["ax"] = {"ln": ("norm",), "mamba": max_}
        return mp

    jax.eval_shape(_one_abs, jax.random.PRNGKey(0))
    layer_axes = cap["ax"]

    def _constrain(p):
        return policy.constrain_tree(p, layer_axes) if policy is not None else p

    def init(key):
        ke, km = jax.random.split(key)
        emb_p, emb_ax = L.embed_init(ke, cfg, dtype)

        def one(kk):
            p, _ = M.mamba_init(kk, cfg, dtype)
            return {"ln": jnp.zeros((cfg.d_model,), dtype), "mamba": p}
        stacked = _stacked_init(km, n, one)
        _, max_ = M.mamba_init(km, cfg, dtype)
        ax = {"ln": ("norm",), "mamba": max_}
        params = {"embed": emb_p, "mamba": stacked,
                  "final_norm": jnp.zeros((cfg.d_model,), dtype)}
        axes = {"embed": emb_ax, "mamba": _stack_axes(ax),
                "final_norm": ("norm",)}
        return params, axes

    def _body_train(h, p):
        p = _constrain(p)
        hn = L.rms_norm(h, p["ln"], cfg.norm_eps)
        return h + M.mamba_block(p["mamba"], cfg, hn)

    def forward(params, inputs):
        h = cb[0](_embed_inputs(cfg, params["embed"], inputs))

        def body(carry, p):
            return _remat(lambda hh, pp: (cb[0](_body_train(hh, pp)), None),
                          cfg.remat_policy)(carry, p)
        h, _ = jax.lax.scan(body, h, params["mamba"])
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h), jnp.zeros((), jnp.float32)

    def prefill(params, inputs, max_seq: int):
        h = _embed_inputs(cfg, params["embed"], inputs)

        def body(hh, p):
            p = _constrain(p)
            hn = L.rms_norm(hh, p["ln"], cfg.norm_eps)
            out, cache = M.mamba_prefill(p["mamba"], cfg, hn)
            return hh + out, cache
        h, caches = jax.lax.scan(body, h, params["mamba"])
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h[:, -1:]), caches

    def decode(params, caches, inputs, pos):
        h = _embed_inputs(cfg, params["embed"], inputs)

        def body(hh, xs):
            p, cache = xs
            p = _constrain(p)
            hn = L.rms_norm(hh, p["ln"], cfg.norm_eps)
            out, nc = M.mamba_decode(p["mamba"], cfg, hn, cache)
            return hh + out, nc
        h, new_caches = jax.lax.scan(body, h, (params["mamba"], caches))
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        return _logits(params["embed"], cfg, h), new_caches

    def init_cache(batch: int, max_seq: int):
        c, ax = M.init_mamba_cache(cfg, batch, dtype)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), c)
        return stacked, _stack_axes(ax)

    return SimpleNamespace(cfg=cfg, init=init, forward=forward,
                           prefill=prefill, decode=decode,
                           init_cache=init_cache,
                           grad_masks=lambda params: None)


# -- hybrid (zamba2): mamba segments + shared attention block ----------------


def _hybrid_layout(cfg):
    seg = cfg.shared_attn_every
    n_apps = cfg.num_layers // seg
    trailing = cfg.num_layers - n_apps * seg
    return seg, n_apps, trailing


SHARED_SUB = None  # set per-config below


def _build_hybrid(cfg, mesh, parallel, policy=None):
    cb = _constrainer(policy)
    dtype = _dtype(cfg)
    seg, n_apps, trailing = _hybrid_layout(cfg)
    shared_sub = Sub(0, cfg.rope_theta, "dense")

    def init(key):
        ke, km, ks = jax.random.split(key, 3)
        emb_p, emb_ax = L.embed_init(ke, cfg, dtype)

        def one(kk):
            p, _ = M.mamba_init(kk, cfg, dtype)
            return {"ln": jnp.zeros((cfg.d_model,), dtype), "mamba": p}
        stacked = _stacked_init(km, cfg.num_layers, one)
        _, max_ = M.mamba_init(km, cfg, dtype)
        m_ax = _stack_axes({"ln": ("norm",), "mamba": max_})
        shared_p, shared_ax = sub_init(ks, cfg, shared_sub, dtype)
        params = {"embed": emb_p, "mamba": stacked, "shared": shared_p,
                  "final_norm": jnp.zeros((cfg.d_model,), dtype)}
        axes = {"embed": emb_ax, "mamba": m_ax, "shared": shared_ax,
                "final_norm": ("norm",)}
        return params, axes

    cap = {}

    def _one_abs(kk):
        mp, max_ = M.mamba_init(kk, cfg, dtype)
        cap["ax"] = {"ln": ("norm",), "mamba": max_}
        return mp

    jax.eval_shape(_one_abs, jax.random.PRNGKey(0))
    layer_axes = cap["ax"]

    def _constrain(p):
        return policy.constrain_tree(p, layer_axes) if policy is not None else p

    def _mamba_scan(stacked, h, mode, caches=None):
        def body(hh, xs):
            if mode == "train":
                p = _constrain(xs)
                hn = L.rms_norm(hh, p["ln"], cfg.norm_eps)
                return hh + M.mamba_block(p["mamba"], cfg, hn), None
            if mode == "prefill":
                p = _constrain(xs)
                hn = L.rms_norm(hh, p["ln"], cfg.norm_eps)
                out, c = M.mamba_prefill(p["mamba"], cfg, hn)
                return hh + out, c
            p, cache = xs
            p = _constrain(p)
            hn = L.rms_norm(hh, p["ln"], cfg.norm_eps)
            out, nc = M.mamba_decode(p["mamba"], cfg, hn, cache)
            return hh + out, nc
        fn = _remat(body, cfg.remat_policy) if mode == "train" else body
        xs = stacked if caches is None else (stacked, caches)
        return jax.lax.scan(fn, h, xs)

    def _slice(tree, a, b):
        return jax.tree.map(lambda x: x[a:b], tree)

    def _run(params, inputs, mode, caches=None, pos=None, max_seq=None):
        if mode == "decode":
            positions = pos[:, None]
        else:
            positions = jnp.arange(inputs.shape[1])[None, :]
        h = _embed_inputs(cfg, params["embed"], inputs)
        h = cb[0](h)
        m_caches, s_caches = (caches if caches is not None else (None, None))
        new_m, new_s = [], []
        for i in range(n_apps):
            blk = _slice(params["mamba"], i * seg, (i + 1) * seg)
            mc = _slice(m_caches, i * seg, (i + 1) * seg) if m_caches is not None else None
            h, yc = _mamba_scan(blk, h, mode, mc)
            if yc is not None:
                new_m.append(yc)
            sc = jax.tree.map(lambda x: x[i], s_caches) if s_caches is not None else None
            h = cb[0](h)
            h, _, nsc = sub_apply(params["shared"], cfg, shared_sub, h,
                                  positions, mode, cache=sc, pos=pos,
                                  max_seq=max_seq, mesh=mesh, parallel=parallel)
            h = cb[0](h)
            if nsc is not None:
                new_s.append(nsc)
        if trailing:
            blk = _slice(params["mamba"], n_apps * seg, cfg.num_layers)
            mc = _slice(m_caches, n_apps * seg, cfg.num_layers) if m_caches is not None else None
            h, yc = _mamba_scan(blk, h, mode, mc)
            if yc is not None:
                new_m.append(yc)
        h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
        new_cache = None
        if mode != "train":
            m_stack = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *new_m)
            s_stack = jax.tree.map(lambda *xs: jnp.stack(xs, 0), *new_s)
            new_cache = (m_stack, s_stack)
        return h, new_cache

    def forward(params, inputs):
        h, _ = _run(params, inputs, "train")
        return _logits(params["embed"], cfg, h), jnp.zeros((), jnp.float32)

    def prefill(params, inputs, max_seq: int):
        h, cache = _run(params, inputs, "prefill", max_seq=max_seq)
        return _logits(params["embed"], cfg, h[:, -1:]), cache

    def decode(params, caches, inputs, pos):
        max_seq = caches[1]["k"].shape[2]
        h, cache = _run(params, inputs, "decode", caches=caches, pos=pos,
                        max_seq=max_seq)
        return _logits(params["embed"], cfg, h), cache

    def init_cache(batch: int, max_seq: int):
        mc, m_ax = M.init_mamba_cache(cfg, batch, dtype)
        m_stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.num_layers,) + x.shape), mc)
        sc, s_ax = init_sub_cache(cfg, shared_sub, batch, max_seq, dtype)
        s_stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_apps,) + x.shape), sc)
        return (m_stacked, s_stacked), (_stack_axes(m_ax), _stack_axes(s_ax))

    return SimpleNamespace(cfg=cfg, init=init, forward=forward,
                           prefill=prefill, decode=decode,
                           init_cache=init_cache,
                           grad_masks=lambda params: None)

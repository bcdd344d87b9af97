"""Speculative decoding: draft-model multi-token decode for the slotted loop.

The serving plane's decode loop emits one token per engine step — the
dominant serving cost once prefill is chunked and cached. Speculative
decoding breaks the one-token-per-step wall while keeping the output
*bit-identical* to non-speculative greedy decode: a cheap **draft** proposes
``k`` candidate tokens per slot, the target model scores all of them in a
single batched ``decode_verify`` call (reusing the chunk-attention
machinery), and the engine accepts the longest prefix of candidates that
matches the target's own greedy choices — emitting the accepted tokens plus
one corrected (or bonus) token per step, between 1 and k+1 tokens per
verify call.

Two drafts are provided:

``NgramDraft``
    Prompt-lookup decoding: propose the continuation that followed the most
    recent earlier occurrence of the context's trailing n-gram (falling back
    to repeating the last token). No parameters, no device state — ideal for
    the pipeline-style traffic this platform serves, where outputs quote and
    repeat their inputs.

``ModelDraft``
    A small same-tokenizer transformer built with ``build_model`` from a
    shrunken copy of the target config. It keeps its own per-slot KV cache
    (placed on the replica's device slice, like the target's) and proposes
    by running k+1 greedy decode steps per engine step. The extra step feeds
    the last proposal back in, so after the engine's accept/reject the draft
    cache is already correct up to the newest emitted token — no per-slot
    catch-up traffic in steady state. Worth it when the draft is genuinely
    cheaper than the target (real accelerators); on a CPU host running tiny
    reduced models every call costs the same dispatch overhead, so the
    n-gram draft is the default.

Rejection needs no cache surgery: verify writes candidate K/V at absolute
positions ``pos..pos+k``, decode/chunk attention masks ``kpos <= pos``, and
the next step's writes land on exactly the positions a rejection
invalidated — so rolling back is just *not advancing* the slot's position
past the accepted prefix.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# Draft protocol
# ---------------------------------------------------------------------------
#
# A draft engine implements:
#
#   propose(items, k) -> np.ndarray (len(items), k) int32
#       ``items`` is a list of ``(slot, request)`` for every slot decoding
#       this step; the request carries the full context (prompt + generated).
#       Proposals are *guesses* — a bad row costs wasted verify compute for
#       that slot, never correctness.
#
# Drafts are per-engine (per-replica) objects: any device state they hold
# lives on the replica's slice and dies with the replica; a failed-over
# request re-syncs on the successor's draft from its context alone.


def _context(request) -> np.ndarray:
    toks = np.asarray(request.tokens, np.int64)
    if request.generated:
        return np.concatenate(
            [toks, np.asarray(request.generated, np.int64)])
    return toks


class NgramDraft:
    """Prompt-lookup draft: continuation after the most recent earlier
    occurrence of the trailing n-gram (n = ``max_ngram`` down to 1), padded
    by repeating the last proposed token; repeat-last when nothing matches.
    Stateless and parameter-free."""

    def __init__(self, max_ngram: int = 3):
        assert max_ngram >= 1
        self.max_ngram = max_ngram

    def propose(self, items: List[tuple], k: int) -> np.ndarray:
        out = np.zeros((len(items), k), np.int32)
        for row, (_slot, r) in enumerate(items):
            out[row] = self._lookup(_context(r), k)
        return out

    def _lookup(self, ctx: np.ndarray, k: int) -> np.ndarray:
        L = len(ctx)
        for n in range(min(self.max_ngram, L - 1), 0, -1):
            pat = ctx[L - n:]
            # most recent occurrence strictly before the trailing pattern,
            # found with one vectorized window comparison per n (a Python
            # scan of per-position array_equal calls is O(L) host work per
            # slot per decode step — on the hot path)
            windows = np.lib.stride_tricks.sliding_window_view(
                ctx[:L - 1], n)                    # starts 0 .. L-1-n
            hits = np.nonzero((windows == pat).all(axis=1))[0]
            if len(hits):
                s = int(hits[-1])
                cont = ctx[s + n:s + n + k]        # s+n <= L-1: never empty
                prop = np.empty((k,), np.int64)
                prop[:len(cont)] = cont
                prop[len(cont):] = cont[-1]
                return prop.astype(np.int32)
        return np.full((k,), ctx[-1], np.int32)


class ModelDraft:
    """Small same-tokenizer transformer draft with its own slotted KV cache.

    The draft's jitted prefill/decode are cached on the draft *model* object
    (like the engine's), so every replica built from the same draft model
    shares one compile. ``devices`` pins the draft's params/cache to the
    replica's slice, beside the target's."""

    def __init__(self, model, params, *, slots: int, max_seq: int,
                 devices=None, prefill_bucket: int = 16, name: str = "draft"):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.name = name
        self.prefill_bucket = max(1, prefill_bucket)
        self.cache, _ = model.init_cache(slots, max_seq)
        self.devices = tuple(devices) if devices else ()
        if self.devices:
            target = self.devices[0]
            self.params = jax.device_put(params, target)
            self.cache = jax.device_put(self.cache, target)
        # per-slot sync state: the request the slot's cache was built for and
        # the exact token ids written at positions [0, len(written)) — the
        # correct-KV prefix at propose time is the longest match between
        # ``written`` and the live context (accepted drafts were correct, so
        # they match; rejected ones diverge and are overwritten in place)
        self._written: List[Optional[np.ndarray]] = [None] * slots
        self._req: List[object] = [None] * slots
        jit_cache = getattr(model, "_draft_jit_cache", None)
        if jit_cache is None:
            jit_cache = {}
            model._draft_jit_cache = jit_cache
        key = (slots, max_seq)
        if key not in jit_cache:
            def decode_fn(p, cache, toks, pos):
                logits, new_cache = model.decode(p, cache, toks, pos)
                nxt = jnp.argmax(logits[:, 0, :model.cfg.vocab_size],
                                 axis=-1).astype(jnp.int32)
                return nxt, new_cache

            def prefill_fn(p, cache, toks, slot, max_seq=max_seq):
                # batch-1 prefill scattered into the slot with a traced
                # index: one compile per bucketed prompt length
                _, row = model.prefill(p, toks, max_seq)
                return jax.tree.map(
                    lambda full, new:
                    jax.lax.dynamic_update_slice_in_dim(full, new, slot, 1),
                    cache, row)
            jit_cache[key] = (jax.jit(decode_fn), jax.jit(prefill_fn))
        self._decode, self._prefill = jit_cache[key]

    # -- sync --------------------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        b = self.prefill_bucket
        return min(self.max_seq, ((n + b - 1) // b) * b)

    def _sync_slot(self, slot: int, r, ctx: np.ndarray):
        """(Re)build the slot's draft cache from the context: needed on a
        slot's first decode step, after slot reuse, and after failover."""
        import jax.numpy as jnp
        n = len(ctx)
        toks = np.zeros((1, self._bucket_len(n)), np.int32)
        toks[0, :n] = ctx
        self.cache = self._prefill(self.params, self.cache,
                                   jnp.asarray(toks), np.int32(slot))
        # padded prefill writes K/V beyond the prompt too, but those
        # positions are masked (kpos <= pos) until real tokens overwrite
        # them — same argument as the engine's padded prefill
        self._written[slot] = np.asarray(ctx, np.int64)
        self._req[slot] = r

    def _synced_len(self, slot: int, r, ctx: np.ndarray) -> int:
        if self._req[slot] is not r or self._written[slot] is None:
            return -1
        w = self._written[slot]
        n = min(len(w), len(ctx))
        eq = w[:n] == ctx[:n]
        return int(n if eq.all() else np.argmin(eq))

    # -- propose -----------------------------------------------------------
    def propose(self, items: List[tuple], k: int) -> np.ndarray:
        import jax.numpy as jnp
        for slot, r in items:
            ctx = _context(r)
            # the draft needs correct KV for every context token but the
            # last (the last is this propose call's first input)
            if self._synced_len(slot, r, ctx) < len(ctx) - 1:
                self._sync_slot(slot, r, ctx)
        toks = np.zeros((self.slots, 1), np.int32)
        pos = np.full((self.slots,), self.max_seq - 1, np.int32)
        ctxs = {}
        for slot, r in items:
            ctx = _context(r)
            ctxs[slot] = ctx
            toks[slot, 0] = int(ctx[-1])
            pos[slot] = len(ctx) - 1
        out = np.zeros((len(items), k), np.int32)
        # k+1 greedy steps: the extra step writes the k-th proposal's K/V,
        # so a fully accepted chain leaves the cache already in sync
        for j in range(k + 1):
            nxt, self.cache = self._decode(self.params, self.cache,
                                           jnp.asarray(toks),
                                           jnp.asarray(pos))
            nxt = np.asarray(nxt)
            for row, (slot, _r) in enumerate(items):
                if j < k:
                    out[row, j] = nxt[slot]
                toks[slot, 0] = nxt[slot]
                pos[slot] += 1
        for row, (slot, _r) in enumerate(items):
            self._written[slot] = np.concatenate(
                [ctxs[slot], out[row].astype(np.int64)])
        return out


# ---------------------------------------------------------------------------
# Draft construction
# ---------------------------------------------------------------------------


def supports_speculation(model, max_seq: int) -> bool:
    """Whether the engine could actually speculate on this model at this
    ``max_seq`` — the same gate ``ServingEngine`` applies (padding-safe,
    all-global attention, and a verify mode). Builders consult it before
    constructing a draft, so a rolling/SSM/MoE service doesn't allocate a
    per-replica draft model + KV cache the engine would never use (and
    re-allocate on every failover/respawn/rebalance)."""
    from repro.serving.engine import _padding_safe
    return _padding_safe(model, max_seq) and \
        getattr(model, "decode_verify", None) is not None


def draft_model_config(cfg):
    """A same-tokenizer shrunken transformer config for ``ModelDraft``:
    half the width, two layers, all-global attention. Only meaningful for
    targets the engine speculates on at all (padding-safe, all-global), so
    the draft is always buildable as a plain dense stack."""
    import dataclasses
    head_dim = cfg.head_dim or 16
    d_model = max(32, (cfg.d_model // 2 // head_dim) * head_dim or head_dim)
    return dataclasses.replace(
        cfg, name=cfg.name + "-draft", family="dense",
        num_layers=min(2, max(1, cfg.num_layers // 2)),
        d_model=d_model, num_heads=2, num_kv_heads=1, head_dim=head_dim,
        d_ff=max(64, cfg.d_ff // 2 if cfg.d_ff else 64),
        moe=None, ssm=None, local_global_pattern=None, sliding_window=0,
        shared_attn_every=0, attn_softcap=0.0,
        remat_policy="none", use_pallas=False)


_DRAFT_MODEL_CACHE: dict = {}
_DRAFT_MODEL_LOCK = threading.Lock()


def draft_model_for(cfg) -> Tuple[object, object]:
    """(model, params) for the draft of target ``cfg``, cached so every
    replica (and every pool generation across failover/rebalance/resize)
    shares one draft model object — and through it one jit cache — the same
    way ``_served_model`` shares the target. Params are deterministic
    (fixed seed), so sharing is observationally identical to rebuilding."""
    import jax

    from repro.models.model import build_model

    key = cfg.name
    with _DRAFT_MODEL_LOCK:
        ent = _DRAFT_MODEL_CACHE.get(key)
        if ent is None:
            dcfg = draft_model_config(cfg)
            model = build_model(dcfg)
            params, _ = model.init(jax.random.PRNGKey(1))
            ent = (model, params)
            _DRAFT_MODEL_CACHE[key] = ent
    return ent


def build_draft(kind: str, target_cfg, *, slots: int, max_seq: int,
                devices=None, name: str = "draft"):
    """Draft factory for one engine replica. ``kind``: ``"ngram"`` (prompt
    lookup, no params) or ``"model"`` (small transformer on the replica's
    device slice)."""
    if kind == "ngram":
        return NgramDraft()
    if kind == "model":
        model, params = draft_model_for(target_cfg)
        return ModelDraft(model, params, slots=slots, max_seq=max_seq,
                          devices=devices, name=name)
    raise ValueError(f"unknown draft kind {kind!r} "
                     f"(expected 'model' or 'ngram')")

"""Serving engine: KV-cache slots, continuous batching, edge routing.

Paper mapping: *edge nodes* (Traefik) load-balance requests over service
replicas; here an ``EdgeRouter`` dispatches generation requests over
data-parallel ``ServingEngine`` replicas, each of which runs a slotted
continuous-batching decode loop (new requests join between decode steps,
finished ones free their slot — the serving analogue of short-lived
containerized tools).

The engine is asynchronous by design: ``start()`` launches the decode loop on
a background thread that admits waiting requests between decode steps, and
``stop()`` signals it through a real ``threading.Event``. The synchronous
``run_until_idle`` path is kept for deterministic single-threaded use (tests,
oracles).

Where padding is safe, each admitted prompt is prefilled alone, its length
padded to a bucket multiple, and never padded to ``slots`` rows: at published
widths a prefill is bound by its compute, so a group padded to 16 rows costs
about 16 times a lone prompt's row, while one more program dispatch per
request is small beside it. Only at a toy size, where every call costs about
its dispatch, would one padded call per admission group be the cheaper.

With ``chunk_tokens`` set (padding-safe models only), long prompts are
*chunk-prefilled*: the prompt enters the per-slot cache in chunk-sized
pieces, one chunk per decode step, so a long admission never stalls tokens
for requests already decoding. Chunk boundaries feed an optional
cross-request ``PrefixCache`` (see ``repro.serving.prefix_cache``): requests
sharing a prompt head restore the deepest cached boundary and recompute only
their tail.

Every program is a named jit (``serve_decode``, ``serve_prefill``,
``serve_chunk``, ...), and each phase of the loop is a profiler annotation
(``serve.admit``, ``serve.prefill``, ``serve.chunk``, ``serve.decode``,
``serve.fetch``, ``serve.emit``, ``serve.wait``; leaves, none inside
another), so a device trace tells the programs apart and names what the
host did in each idle gap. With no trace running, an annotation costs the
profiler's inactive check.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from concurrent.futures import Future
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.observability.tracing import NULL_TRACE, TraceContext, next_rid


@dataclasses.dataclass
class Request:
    tokens: np.ndarray          # prompt (prompt_len,)
    max_new_tokens: int = 16
    eos_id: int = -1            # -1: never stop early
    future: Future = dataclasses.field(default_factory=Future)
    slot: int = -1
    generated: list = dataclasses.field(default_factory=list)
    submit_t: float = dataclasses.field(default_factory=time.perf_counter)
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    rid: int = dataclasses.field(default_factory=next_rid)
    # NULL_TRACE when the flight recorder is off: every trace call site is
    # an unconditional no-op method on the shared singleton
    trace: object = NULL_TRACE

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def latency_s(self) -> Optional[float]:
        if self.done_t is None:
            return None
        return self.done_t - self.submit_t

    retries: int = 0

    def reset_for_retry(self):
        """Failover: forget partial progress; greedy decode is deterministic,
        so a fresh run on another replica produces the same tokens."""
        self.slot = -1
        self.generated = []
        self.first_token_t = None
        self.retries += 1
        # the re-queued request waits again: a failed-over record shows a
        # second queue_wait span after the failover event (any phase span
        # left open by the dead replica ends here)
        self.trace.close("prefill")
        self.trace.close("decode")
        self.trace.open("queue_wait", retry=self.retries)


def _padding_safe(model, max_seq: int) -> bool:
    """Right-padded prefill is exact only when every sub-layer is
    global attention at this ``max_seq``: decode overwrites cache position
    ``pos`` before attending, so pad garbage beyond the prompt is never read.
    Rolling (sliding-window) caches place the *last W of the padded length*
    — pad rows would evict real prompt positions — recurrent SSM state
    absorbs pad tokens, and MoE capacity routing is shared across all
    flattened batch tokens (pad rows would consume expert capacity and shift
    real tokens' routing); all of those need exact per-length groups with no
    pad rows instead."""
    subs = getattr(model, "subs", None)
    if subs is None:
        return False
    if any(s.ffn == "moe" for s in subs):
        return False
    return all(s.window == 0 or s.window >= max_seq for s in subs)


class ServingEngine:
    """Slotted continuous batching over a fixed decode batch.

    ``devices`` assigns this replica a slice of the VRE mesh: params and the
    KV cache are ``jax.device_put`` onto it (replicated across the slice when
    it holds more than one device), so replicas genuinely occupy disjoint
    hardware instead of all sharing the default device. With ``devices=None``
    the engine keeps the old uncommitted default-device behavior."""

    def __init__(self, model, params, *, slots: int = 4, max_seq: int = 256,
                 name: str = "engine0", monitor=None, prefill_bucket: int = 16,
                 devices=None, chunk_tokens: Optional[int] = None,
                 prefix_cache=None, speculate: int = 0, draft=None,
                 recorder=None):
        self.model = model
        self.cfg = model.cfg
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.name = name
        self.monitor = monitor
        # flight recorder: an attached recorder implies tracing — requests
        # get a TraceContext at submit and a JSONL record at completion
        self.recorder = recorder
        self.prefill_bucket = max(1, prefill_bucket)
        self.chunk_tokens = int(chunk_tokens) if chunk_tokens else 0
        self.prefix_cache = prefix_cache
        self.speculate = int(speculate) if speculate else 0
        self.draft = draft
        self.cache, _ = model.init_cache(slots, max_seq)
        self.devices = tuple(devices) if devices else ()
        if self.devices:
            if len(self.devices) == 1:
                target = self.devices[0]
            else:
                from jax.sharding import (Mesh, NamedSharding,
                                          PartitionSpec)
                slice_mesh = Mesh(np.array(self.devices), ("slice",))
                target = NamedSharding(slice_mesh, PartitionSpec())
            # committed inputs pin every jitted prefill/decode call (and its
            # outputs) to this replica's slice
            self.params = jax.device_put(params, target)
            self.cache = jax.device_put(self.cache, target)
        self.pos = np.zeros((slots,), np.int32) - 1    # -1: free slot
        self.active: List[Optional[Request]] = [None] * slots
        # slot -> next prompt position to prefill; a slot present here holds
        # an admitted request still being chunk-prefilled (it is excluded
        # from decode until its prompt is fully in cache)
        self._prefilling: dict = {}
        self.queue: "queue.Queue[Request]" = queue.Queue()
        self.metrics = {"requests": 0, "tokens": 0, "prefills": 0,
                        "prefill_requests": 0, "decode_steps": 0,
                        "completed": 0, "prefill_chunks": 0,
                        "prefill_tokens": 0, "prefix_hit_tokens": 0,
                        "prefill_chunk_batches": 0, "spec_steps": 0,
                        "spec_proposed": 0, "spec_accepted": 0,
                        "spec_emitted": 0,
                        # token positions prefill programs compute for
                        # prompts (pad rows and pad columns included), and
                        # the real prompt positions among them
                        "prefill_positions_computed": 0,
                        "prefill_positions_real": 0,
                        # host time from a step's token fetch to the loop's
                        # next program call, waits excluded: the device has
                        # nothing queued meanwhile
                        "host_gap_s": 0.0}
        self._fetched_t: Optional[float] = None
        # jitted prefill/decode are shared across all engines with the same
        # (model, slots, max_seq): replicas and failover respawns then reuse
        # one compile instead of paying it per replica. Prefill sees the
        # padded (1, bucketed_len) shape where padding is safe, so repeat
        # admissions hit the compile cache instead of re-tracing.
        jit_cache = getattr(model, "_engine_jit_cache", None)
        if jit_cache is None:
            jit_cache = {}
            model._engine_jit_cache = jit_cache
        key = (slots, max_seq)
        if key not in jit_cache:
            # named functions, so each program keeps one name in a device
            # trace (``jit_serve_decode``, ...)
            def serve_decode(p, c, t, pos):
                return model.decode(p, c, t, pos)

            def serve_prefill(p, t):
                return model.prefill(p, t, max_seq)[1]
            jit_cache[key] = (jax.jit(serve_decode), jax.jit(serve_prefill))
        self._decode, self._prefill = jit_cache[key]
        self._pad_ok = _padding_safe(model, max_seq)
        # chunked prefill is exact only where padded prefill is (all-global
        # attention: chunk K/V writes land at absolute positions and the
        # chunk mask is position-based); rolling/SSM/MoE models keep the
        # whole-prompt path
        self._chunk_ok = bool(self.chunk_tokens) and self._pad_ok and \
            getattr(model, "prefill_chunk", None) is not None
        if self.chunk_tokens and not self._chunk_ok and monitor is not None:
            monitor.log(name, "chunked_prefill_unsupported",
                        reason="model is not padding-safe (rolling/SSM/MoE)"
                        if getattr(model, "prefill_chunk", None) is not None
                        else "model has no prefill_chunk")
        if self._chunk_ok:
            ckey = (slots, max_seq, self.chunk_tokens)
            if ckey not in jit_cache:
                def serve_chunk(p, cache, toks, pos0, slot):
                    # slice one slot out of the batched cache, run the chunk
                    # against it, scatter the updated slice back — slot and
                    # pos0 are traced, so one compile serves every slot and
                    # chunk offset
                    sl = jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, 1),
                        cache)
                    _, new_sl = model.prefill_chunk(p, sl, toks, pos0)
                    return jax.tree.map(
                        lambda full, s:
                        jax.lax.dynamic_update_slice_in_dim(full, s, slot, 1),
                        cache, new_sl)
                jit_cache[ckey] = jax.jit(serve_chunk)
            self._chunk = jit_cache[ckey]
            # batched variant: when several slots are mid-chunking, gather
            # each one's cache slice into a batch row and advance them all
            # in ONE call instead of one batch-1 dispatch per slot. Rides
            # on the same padding-safe gate as chunking itself (per-row
            # pos0/positions are exact for all-global attention); rows are
            # padded to `slots` so the compile is shape-stable — pad rows
            # duplicate row 0, whose identical scatter writes are benign.
            bkey = (slots, max_seq, self.chunk_tokens, "chunk_batched")
            if bkey not in jit_cache:
                def serve_chunk_batch(p, cache, toks, pos0s, slots_arr):
                    sl = jax.tree.map(
                        lambda x: jnp.take(x, slots_arr, axis=1), cache)
                    _, new_sl = model.prefill_chunk(p, sl, toks, pos0s)
                    return jax.tree.map(
                        lambda full, s: full.at[:, slots_arr].set(s),
                        cache, new_sl)
                jit_cache[bkey] = jax.jit(serve_chunk_batch)
            self._chunk_batched = jit_cache[bkey]
            # prefix-cache restore/extract with a *traced* slot index: a
            # plain eager cache.at[:, slot, :L].set() bakes the slot in as
            # a constant and recompiles per slot, which showed up as ~200ms
            # admission stalls. One compile per prefix length L instead.
            pkey = (slots, max_seq, "prefix")
            if pkey not in jit_cache:
                def serve_prefix_restore(cache, entry, slot):
                    return jax.tree.map(
                        lambda full, ent: jax.lax.dynamic_update_slice(
                            full, ent[:, None].astype(full.dtype),
                            (0, slot) + (0,) * (full.ndim - 2)),
                        cache, entry)

                def serve_prefix_extract(cache, slot, start, length):
                    # start is traced (the slice length is always one chunk,
                    # so a static start would recompile per boundary offset)
                    return jax.tree.map(
                        lambda x: jax.lax.dynamic_slice_in_dim(
                            jax.lax.dynamic_slice_in_dim(x, slot, 1, 1),
                            start, length, 2)[:, 0],
                        cache)
                jit_cache[pkey] = (jax.jit(serve_prefix_restore),
                                   jax.jit(serve_prefix_extract,
                                           static_argnums=3))
            self._pc_restore, self._pc_extract = jit_cache[pkey]
        # speculative decode rides the same padding-safety gate as chunking
        # (verify writes candidate K/V at absolute positions and relies on
        # the position-based chunk mask); models without a verify mode
        # (rolling/SSM/hybrid) degrade cleanly to k=1 — the plain fused
        # decode — and a missing draft means nothing to verify
        self._spec_ok = bool(self.speculate) and self._pad_ok and \
            self.draft is not None and \
            getattr(model, "decode_verify", None) is not None
        if self.speculate and not self._spec_ok and monitor is not None:
            if getattr(model, "decode_verify", None) is None:
                reason = "model has no decode_verify (rolling/SSM/hybrid)"
            elif not self._pad_ok:
                reason = "model is not padding-safe (rolling/SSM/MoE)"
            else:
                reason = "no draft engine configured"
            monitor.log(name, "speculative_unsupported", reason=reason,
                        speculate=self.speculate)
        if self._spec_ok:
            vkey = (slots, max_seq, self.speculate, "verify")
            if vkey not in jit_cache:
                def serve_verify(p, cache, toks, pos):
                    # greedy argmax in-graph: the engine only needs the
                    # target's token choices, not (slots, K+1, V) f32 logits
                    # on the host every step
                    logits, new_cache = model.decode_verify(p, cache, toks,
                                                            pos)
                    greedy = jnp.argmax(
                        logits[..., :model.cfg.vocab_size],
                        axis=-1).astype(jnp.int32)
                    return greedy, new_cache
                jit_cache[vkey] = jax.jit(serve_verify)
            self._verify = jit_cache[vkey]
        # -- async decode loop state --------------------------------------
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._killed = False
        self.heartbeat = time.monotonic()

    # -- request API ------------------------------------------------------
    def submit_request(self, tokens, max_new_tokens=16, eos_id=-1) -> Request:
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim != 1 or not len(tokens):
            raise ValueError(f"prompt must be a non-empty 1-D token array, "
                             f"got shape {tokens.shape}")
        if len(tokens) + 1 > self.max_seq:
            raise ValueError(f"prompt of {len(tokens)} tokens leaves no room "
                             f"to generate within max_seq={self.max_seq}")
        r = Request(tokens, max_new_tokens, eos_id)
        if self.recorder is not None:
            r.trace = TraceContext("request", rid=r.rid,
                                   prompt_len=len(tokens),
                                   max_new_tokens=max_new_tokens)
            r.trace.open("queue_wait")
        self.queue.put(r)
        self.metrics["requests"] += 1
        self._wake.set()
        return r

    def submit(self, tokens, max_new_tokens=16, eos_id=-1) -> Future:
        return self.submit_request(tokens, max_new_tokens, eos_id).future

    # -- batched admission -------------------------------------------------
    def _bucket_len(self, n: int) -> int:
        b = self.prefill_bucket
        return min(self.max_seq, ((n + b - 1) // b) * b)

    def _prefill_group(self, grp: List[Request]):
        """Prefill a group of newly admitted requests into their slots.

        When padding is safe, each request takes a call of its own: one row,
        its length padded to a bucket multiple, so the jitted prefill
        compiles once per bucket whatever the group's size, and a request
        that fails fails alone. Rows are not padded to ``slots``: at
        published widths a pad row costs as much compute as a real one.
        Rolling/SSM/MoE groups are same-length and take one exact call,
        with no pad rows and no pad columns, since length padding would wrap
        the rolling cache (evicting real prompt positions) or feed pad tokens
        into recurrent state, and pad rows would consume MoE expert
        capacity; such a group fails as a unit."""
        if not self._pad_ok:
            for r in grp:
                r.trace.open("prefill", mode="batched", group=len(grp))
            self._prefill_rows(grp)
            return
        for r in grp:
            r.trace.open("prefill", mode="single", group=len(grp))
        for r in grp:
            try:
                self._prefill_rows([r])
            except Exception as exc:
                self._fail_prefill([r], exc)

    def _prefill_rows(self, reqs: List[Request]):
        """One ``serve_prefill`` call, a row per request, and the write of
        each row into its request's slot."""
        n = max(len(r.tokens) for r in reqs)
        if self._pad_ok:
            n = self._bucket_len(n)
        toks = np.zeros((len(reqs), n), np.int32)
        for j, r in enumerate(reqs):
            toks[j, :len(r.tokens)] = r.tokens
        rows_cache = self._call(self._prefill, self.params, jnp.asarray(toks))
        self.metrics["prefill_positions_computed"] += toks.size
        self.metrics["prefill_positions_real"] += sum(len(r.tokens)
                                                      for r in reqs)
        slots_arr = jnp.asarray([r.slot for r in reqs], jnp.int32)
        self.cache = jax.tree.map(
            lambda full, new: full.at[:, slots_arr].set(new),
            self.cache, rows_cache)
        self.metrics["prefills"] += 1
        self.metrics["prefill_requests"] += len(reqs)
        for r in reqs:
            self.pos[r.slot] = len(r.tokens) - 1
            self.active[r.slot] = r
            r.trace.close("prefill", tokens=len(r.tokens))
            r.trace.open("decode")

    def _fail_prefill(self, reqs: List[Request], exc: Exception):
        """Fail requests whose prefill raised: they were already pulled off
        the queue, so an unhandled raise would strand them."""
        for r in reqs:
            r.slot = -1
            if not r.future.done():
                r.future.set_exception(exc)
        if self.monitor is not None:
            self.monitor.log(self.name, "prefill_error",
                             error=repr(exc), requests=len(reqs))

    def _admit(self):
        """Fill free slots from the queue: long prompts (and any prompt when
        a prefix cache may hold its head) enter the chunk-wise prefill
        state; the rest are prefilled whole, as one admission group (per
        prompt-length group when padding is unsafe)."""
        batch: List[Request] = []
        with TraceAnnotation("serve.admit"):
            for slot in range(self.slots):
                if self.active[slot] is not None:
                    continue
                try:
                    r = self.queue.get_nowait()
                except queue.Empty:
                    break
                r.slot = slot
                r.trace.close("queue_wait", replica=self.name, slot=slot)
                if self.monitor is not None:
                    # queue-wait is an SLO surface of its own: load gauges
                    # count *requests* waiting, this measures how long they
                    # waited — long generations at low concurrency hurt here
                    # first
                    self.monitor.gauge(self.name, "queue_wait_s",
                                       time.perf_counter() - r.submit_t)
                # chunked admission for prompts longer than one chunk, or
                # ones a prefix cache could serve (>= one chunk boundary);
                # sub-chunk prompts can neither hit nor seed the cache, so
                # they keep the whole-prompt prefill
                if self._chunk_ok and (
                        len(r.tokens) > self.chunk_tokens
                        or (self.prefix_cache is not None
                            and len(r.tokens) >= self.chunk_tokens)):
                    self._admit_chunked(r)
                else:
                    batch.append(r)
        if not batch:
            return
        if self._pad_ok:
            groups = [batch]
        else:                   # rolling/SSM/MoE: exact lengths, no pad rows
            by_len = {}
            for r in batch:
                by_len.setdefault(len(r.tokens), []).append(r)
            groups = list(by_len.values())
        for grp in groups:
            try:
                with TraceAnnotation("serve.prefill"):
                    self._prefill_group(grp)
            except Exception as exc:
                self._fail_prefill(grp, exc)

    # -- chunked prefill ---------------------------------------------------
    def _admit_chunked(self, r: Request):
        """Admit a request into the chunk-wise prefill state, restoring the
        deepest prefix-cache boundary first so only the uncovered tail is
        computed."""
        start = 0
        span = r.trace.open("prefill", mode="chunked")
        if self.prefix_cache is not None:
            covered, entry = self.prefix_cache.lookup(r.tokens)
            if covered:
                try:
                    self.cache = self._call(
                        self._pc_restore, self.cache,
                        jax.tree.map(jnp.asarray, entry), np.int32(r.slot))
                    start = covered
                    self.metrics["prefix_hit_tokens"] += covered
                    span.annotate(prefix_hit_tokens=covered)
                    r.trace.event("prefix_cache_hit", tokens=covered)
                except Exception as exc:
                    # a bad entry (e.g. adopted from an incompatible pool)
                    # must degrade to a miss — an unhandled raise here would
                    # strand the already-dequeued request forever and fail
                    # every other in-flight request via _fail_inflight
                    start = 0
                    r.trace.event("prefix_restore_error")
                    if self.monitor is not None:
                        self.monitor.log(self.name, "prefix_restore_error",
                                         error=repr(exc), covered=covered)
        self.active[r.slot] = r
        if start >= len(r.tokens):
            # the whole prompt was cached: straight to decode (the first
            # decode step recomputes the last prompt token at pos len-1,
            # overwriting its cached K/V with identical values)
            self.pos[r.slot] = len(r.tokens) - 1
            self.metrics["prefill_requests"] += 1
            r.trace.close("prefill", tokens=len(r.tokens))
            r.trace.open("decode")
        else:
            self.pos[r.slot] = -1           # not decoding yet
            self._prefilling[r.slot] = start

    def _prefill_step(self):
        """Advance every chunk-prefilling slot by one chunk. Runs before the
        fused decode step, so long prompts trickle in between decode steps
        instead of stalling already-admitted requests. Two or more
        concurrent chunking slots advance in a single batched call; a lone
        slot keeps the batch-1 kernel (padding it to ``slots`` rows would
        multiply its compute for nothing)."""
        items = list(self._prefilling.items())
        if len(items) >= 2:
            self._prefill_chunks_batched(items)
            return
        for slot, start in items:
            r = self.active[slot]
            plen = len(r.tokens)
            c = self.chunk_tokens
            end = min(start + c, plen)
            toks = np.zeros((1, c), np.int32)   # final partial chunk padded:
            toks[0, :end - start] = r.tokens[start:end]   # one compile per C
            try:
                self.cache = self._call(
                    self._chunk, self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray([start], jnp.int32), np.int32(slot))
            except Exception as exc:
                del self._prefilling[slot]
                self.active[slot] = None
                self.pos[slot] = -1
                if not r.future.done():
                    r.future.set_exception(exc)
                if self.monitor is not None:
                    self.monitor.log(self.name, "prefill_error",
                                     error=repr(exc), requests=1)
                continue
            self.metrics["prefill_positions_computed"] += c
            self.metrics["prefill_positions_real"] += end - start
            self._after_chunk(slot, start, end, r)

    def _prefill_chunks_batched(self, items):
        """One engine call advances every chunk-prefilling slot: rows gather
        the per-slot cache slices, run the chunk with per-row pos0, and
        scatter back. Rows are padded to ``slots`` by duplicating row 0 (the
        duplicate writes the same values to the same slot — benign), so the
        call compiles once regardless of how many slots are chunking."""
        c = self.chunk_tokens
        toks = np.zeros((self.slots, c), np.int32)
        pos0 = np.zeros((self.slots,), np.int32)
        slot_idx = np.zeros((self.slots,), np.int32)
        rows = []
        for j, (slot, start) in enumerate(items):
            r = self.active[slot]
            end = min(start + c, len(r.tokens))
            toks[j, :end - start] = r.tokens[start:end]
            pos0[j] = start
            slot_idx[j] = slot
            rows.append((slot, start, end, r))
        toks[len(items):] = toks[0]
        pos0[len(items):] = pos0[0]
        slot_idx[len(items):] = slot_idx[0]
        try:
            self.cache = self._call(
                self._chunk_batched, self.params, self.cache,
                jnp.asarray(toks), jnp.asarray(pos0), jnp.asarray(slot_idx))
        except Exception as exc:
            # the batch failed as a unit: every participating request fails
            for slot, _start, _end, r in rows:
                self._prefilling.pop(slot, None)
                self.active[slot] = None
                self.pos[slot] = -1
                if not r.future.done():
                    r.future.set_exception(exc)
            if self.monitor is not None:
                self.monitor.log(self.name, "prefill_error",
                                 error=repr(exc), requests=len(rows))
            return
        self.metrics["prefill_chunk_batches"] += 1
        self.metrics["prefill_positions_computed"] += toks.size
        self.metrics["prefill_positions_real"] += sum(
            end - start for _slot, start, end, _r in rows)
        for slot, start, end, r in rows:
            self._after_chunk(slot, start, end, r)

    def _after_chunk(self, slot: int, start: int, end: int, r: Request):
        """Shared post-chunk bookkeeping: metrics, prefix-cache insertion at
        chunk boundaries, and the prefilling -> decoding transition."""
        c = self.chunk_tokens
        self.metrics["prefill_chunks"] += 1
        self.metrics["prefill_tokens"] += end - start
        r.trace.event("chunk", start=start, end=end)
        if self.prefix_cache is not None and end % c == 0 \
                and not self.prefix_cache.contains(r.tokens[:end]):
            # the cache stores per-chunk slices: offer only this
            # chunk's [end-c, end) positions (the trie chain supplies
            # the rest on restore)
            entry = self._call(self._pc_extract, self.cache, np.int32(slot),
                               np.int32(end - c), c)
            self.prefix_cache.insert(r.tokens[:end], entry)
        if end >= len(r.tokens):
            del self._prefilling[slot]
            self.pos[slot] = len(r.tokens) - 1       # ready for decode
            self.metrics["prefill_requests"] += 1
            r.trace.close("prefill", tokens=len(r.tokens))
            r.trace.open("decode")
        else:
            self._prefilling[slot] = end

    @property
    def prefill_backlog(self) -> int:
        """Prompt tokens admitted-or-queued but not yet in a KV cache — the
        admission pressure signal (queue depth alone under-counts a backlog
        of long prompts). Read from the autoscaler thread while the decode
        loop mutates: list(deque) / dict(dict) are C-level (GIL-atomic)
        snapshots, and a racing slot reuse only skews the gauge briefly."""
        queued = sum(len(r.tokens) for r in list(self.queue.queue))
        chunking = 0
        for s, p in dict(self._prefilling).items():
            r = self.active[s]
            if r is not None:
                chunking += len(r.tokens) - p
        return queued + chunking

    # -- decode step -------------------------------------------------------
    def step(self) -> int:
        """One fused decode (or speculative verify) step for all active
        slots. Returns #active."""
        self._admit()
        if self._prefilling:
            with TraceAnnotation("serve.chunk"):
                self._prefill_step()
        active = [i for i in range(self.slots)
                  if self.active[i] is not None and i not in self._prefilling]
        if not active:
            return len(self._prefilling)
        if self._spec_ok:
            self._spec_step(active)
        else:
            self._decode_step(active)
        return len(active) + len(self._prefilling)

    def _call(self, program, *args):
        """Call one of the engine's programs; the first call after a token
        fetch closes the host gap that the fetch opened."""
        if self._fetched_t is not None:
            self.metrics["host_gap_s"] += time.perf_counter() - self._fetched_t
            self._fetched_t = None
        return program(*args)

    def _emit_token(self, i: int, r: Request, tok: int, now: float) -> bool:
        """Record one generated token for slot ``i`` — the single source of
        the stop conditions (budget, EOS, sequence limit), shared by the
        plain decode step and the speculative emission loop so the two paths
        cannot disagree on when a request completes. Returns done."""
        if not r.generated:
            r.first_token_t = now
            if self.monitor is not None:
                self.monitor.gauge(self.name, "ttft_s", r.ttft_s)
        r.generated.append(tok)
        self.metrics["tokens"] += 1
        self.pos[i] += 1
        done = (len(r.generated) >= r.max_new_tokens or tok == r.eos_id
                or self.pos[i] + 1 >= self.max_seq)
        if done:
            r.done_t = now
            self.metrics["completed"] += 1
            if self.monitor is not None:
                self.monitor.gauge(self.name, "latency_s", r.latency_s)
            r.trace.close("decode", tokens=len(r.generated))
            if self.recorder is not None:
                self.recorder.record(r, self)
            if not r.future.done():     # a detach may have failed the
                r.future.set_result(    # future out from under a stuck
                    np.asarray(r.generated, np.int32))   # decode loop
            self.active[i] = None
            self.pos[i] = -1
        return done

    def _decode_step(self, active: List[int]):
        """One fused single-token decode over ``active``."""
        with TraceAnnotation("serve.decode"):
            toks = np.zeros((self.slots, 1), np.int32)
            # idle / still-prefilling rows decode a scratch token at position
            # max_seq-1 (never written or attended by a real request:
            # admission requires len+1 <= max_seq and decode stops at
            # pos+1 >= max_seq), so the fused decode can't clobber a
            # half-prefilled slot's cache
            pos = np.full((self.slots,), self.max_seq - 1, np.int32)
            for i in active:
                r = self.active[i]
                toks[i, 0] = (r.generated[-1] if r.generated
                              else int(r.tokens[-1]))
                pos[i] = max(int(self.pos[i]), 0)
            logits, self.cache = self._call(
                self._decode, self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos))
            greedy = jnp.argmax(logits[:, 0, :self.cfg.vocab_size], axis=-1)
        with TraceAnnotation("serve.fetch"):
            next_tokens = np.asarray(greedy)
        now = self._fetched_t = time.perf_counter()
        with TraceAnnotation("serve.emit"):
            self.metrics["decode_steps"] += 1
            for i in active:
                self._emit_token(i, self.active[i], int(next_tokens[i]), now)

    def _spec_step(self, active: List[int]):
        """One speculative verify step over ``active``: the draft proposes
        k tokens per slot, ``decode_verify`` greedily scores every candidate
        position in one batched call, and each slot emits the longest
        matching prefix plus one corrected (or, on full acceptance, bonus)
        token — 1..k+1 tokens per step, bit-identical to the plain decode
        path. Idle / still-prefilling rows ride along as scratch rows at
        position max_seq-1 (in-bounds writes land on the scratch position,
        overflowing candidate positions are dropped by the scatter), exactly
        like the fused decode."""
        k = self.speculate
        items = [(i, self.active[i]) for i in active]
        with TraceAnnotation("serve.decode"):
            props = np.asarray(self.draft.propose(items, k), np.int32)
            toks = np.zeros((self.slots, k + 1), np.int32)
            pos = np.full((self.slots,), self.max_seq - 1, np.int32)
            for row, (i, r) in enumerate(items):
                toks[i, 0] = (r.generated[-1] if r.generated
                              else int(r.tokens[-1]))
                toks[i, 1:] = props[row]
                pos[i] = max(int(self.pos[i]), 0)
            greedy, self.cache = self._call(
                self._verify, self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(pos))
        with TraceAnnotation("serve.fetch"):
            greedy = np.asarray(greedy)                  # (slots, k+1)
        now = self._fetched_t = time.perf_counter()
        with TraceAnnotation("serve.emit"):
            self._spec_emit(active, toks, greedy, now)

    def _spec_emit(self, active: List[int], toks, greedy, now: float):
        """Accept each slot's longest matching candidate prefix and emit
        it; the bookkeeping after a verify step's fetch."""
        k = self.speculate
        self.metrics["decode_steps"] += 1
        self.metrics["spec_steps"] += 1
        accepted = emitted = 0
        for i in active:
            r = self.active[i]
            m = 0       # accepted draft prefix: d_j must equal the target's
            while m < k and toks[i, m + 1] == greedy[i, m]:   # own greedy
                m += 1                                        # choice g_j
            accepted += m
            r.trace.event("verify", proposed=k, accepted=m)
            # emit g_0..g_m: the m accepted candidates plus the correction
            # (m < k) or bonus (m == k) token; the stop conditions run
            # per-token, so EOS / budget / seq-limit truncate mid-chain
            # exactly where the non-speculative loop would stop
            for j in range(m + 1):
                emitted += 1
                if self._emit_token(i, r, int(greedy[i, j]), now):
                    break
        self.metrics["spec_proposed"] += len(active) * k
        self.metrics["spec_accepted"] += accepted
        self.metrics["spec_emitted"] += emitted
        if self.monitor is not None:
            self.monitor.gauge(self.name, "spec_accept_rate",
                               accepted / (len(active) * k))
            self.monitor.gauge(self.name, "spec_tokens_per_step",
                               emitted / len(active))

    # -- synchronous loop (tests / oracles) --------------------------------
    def run_until_idle(self, max_steps: int = 10_000):
        assert not self.running, "run_until_idle on a started engine"
        self._fetched_t = None      # no host gap across separate runs
        steps = 0
        while (not self.queue.empty() or any(a is not None
                                             for a in self.active)):
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("serving loop did not drain")
        return steps

    # -- async decode loop -------------------------------------------------
    def start(self):
        if self.running:
            return self
        self._stop.clear()
        self._killed = False
        self._fetched_t = None
        self.heartbeat = time.monotonic()
        self._thread = threading.Thread(target=self._loop,
                                        name=f"{self.name}-decode",
                                        daemon=True)
        self._thread.start()
        return self

    def _loop(self):
        while not self._stop.is_set():
            if self._killed:        # simulated container crash: loop dies,
                return              # heartbeat freezes, requests strand
            self.heartbeat = time.monotonic()
            try:
                n = self.step()
            except Exception as exc:
                # a poisoned request must not kill the replica (a dead loop
                # would re-queue it via failover and crash the next replica
                # too): fail everything currently on this engine with the
                # error and keep serving new work
                self._fail_inflight(exc)
                n = 0
            # refresh after the step too: a single long step (first-call
            # compile) must not read as a dead container to the health sweep
            self.heartbeat = time.monotonic()
            if n == 0:
                with TraceAnnotation("serve.wait"):
                    t = time.perf_counter()
                    self._wake.wait(timeout=0.005)
                    if self._fetched_t is not None:
                        # waiting for work is no part of the host gap
                        self._fetched_t += time.perf_counter() - t
                self._wake.clear()

    def _fail_inflight(self, exc: Exception):
        """Fail the requests in active slots (a decode error affects exactly
        those); queued requests keep their chance — if the error is
        systemic they fail one admission wave at a time, so the engine
        still drains instead of looping."""
        reqs = []
        for i in range(self.slots):
            if self.active[i] is not None:
                reqs.append(self.active[i])
            self.active[i] = None
            self.pos[i] = -1
        self._prefilling.clear()
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(exc)
        if self.monitor is not None:
            self.monitor.log(self.name, "step_error", error=repr(exc),
                             failed_requests=len(reqs))

    def stop(self, timeout: float = 10.0) -> bool:
        """Signal the decode loop and join it. Returns False if the thread
        is still running after ``timeout`` (e.g. blocked in a long compile)
        — the caller must NOT harvest until a later stop() succeeds."""
        self._stop.set()
        self._wake.set()
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                return False
        self._thread = None
        return True

    def kill(self):
        """Simulate a container crash: the decode loop exits without
        cleanup, health goes red, in-flight requests are stranded until a
        ReplicaSet reschedules them."""
        self._killed = True
        self._wake.set()

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def healthy(self) -> bool:
        """True iff the engine can make progress on new work: not killed,
        not stop()ped, and (if started) the decode loop is alive. A
        never-started engine is healthy — the synchronous run_until_idle
        path drives it without a thread."""
        if self._killed or self._stop.is_set():
            return False
        if self._thread is not None:
            return self._thread.is_alive()
        return True

    def wait_idle(self, timeout: float = 60.0) -> bool:
        """Block until queue+slots are empty (async engines only)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.load == 0:
                return True
            if not self.running and not self._stop.is_set() \
                    and self._thread is not None:
                return False        # loop died with work pending
            time.sleep(0.002)
        return False

    def harvest_requests(self) -> List[Request]:
        """Strip all incomplete requests (queued + in-flight) off this
        engine, resetting their progress so they can be rescheduled. Call
        only after the decode loop has exited."""
        assert not self.running, "harvest from a live decode loop"
        out: List[Request] = []
        while True:
            try:
                out.append(self.queue.get_nowait())
            except queue.Empty:
                break
        for i in range(self.slots):
            r = self.active[i]
            if r is not None and not r.future.done():
                out.append(r)
            self.active[i] = None
            self.pos[i] = -1
        self._prefilling.clear()
        for r in out:
            r.reset_for_retry()
        return out

    @property
    def load(self) -> int:
        return self.queue.qsize() + sum(a is not None for a in self.active)

    @property
    def device_set(self) -> frozenset:
        """Devices this replica's params actually live on — placement truth
        (read from the arrays), not just the requested slice."""
        if not self.devices:
            return frozenset()
        return frozenset(jax.tree.leaves(self.params)[0].devices())


class EdgeRouter:
    """Traefik analogue: least-loaded dispatch over healthy engine replicas.

    Accepts either a plain engine list or a lifecycle-managed
    ``repro.serving.replica.ReplicaSet`` (duck-typed via ``.engines``)."""

    def __init__(self, engines):
        self._source = engines if hasattr(engines, "engines") else None
        self._engines = [] if self._source else list(engines)
        assert self._engines or self._source

    @property
    def engines(self) -> List[ServingEngine]:
        # always re-read from the ReplicaSet: scale_to/failover rebind its
        # list, so a stored alias would go stale
        return self._source.engines if self._source else self._engines

    def _pool(self) -> List[ServingEngine]:
        healthy = [e for e in self.engines if e.healthy()]
        if not healthy:
            raise RuntimeError("no healthy serving replicas")
        return healthy

    def submit_request(self, tokens, **kw) -> Request:
        if self._source is not None:
            # the ReplicaSet must choose-and-enqueue under its own lock so
            # the request can't land on an engine after its final harvest
            return self._source.submit_request(tokens, **kw)
        eng = min(self._pool(), key=lambda e: e.load)
        return eng.submit_request(tokens, **kw)

    def submit(self, tokens, **kw) -> Future:
        return self.submit_request(tokens, **kw).future

    def drain(self, timeout: float = 120.0):
        if self._source is not None:
            # ReplicaSet: failover may move work between engines mid-drain,
            # so wait on the aggregate instead of per-engine queues
            if not self._source.wait_all(timeout):
                raise RuntimeError("replica set did not drain")
            return
        for e in self.engines:      # every engine — a dead one must not be
            if e.running:           # silently skipped with queued requests
                if not e.wait_idle(timeout):
                    raise RuntimeError(f"{e.name} did not drain")
            elif e.healthy():
                e.run_until_idle()
            elif e.load:
                raise RuntimeError(f"{e.name} is dead with {e.load} "
                                   f"undrained requests")

    def metrics(self):
        return {e.name: dict(e.metrics) for e in self.engines}


def greedy_generate(model, params, prompt: np.ndarray, max_new_tokens: int,
                    max_seq: int) -> np.ndarray:
    """Reference generation: prefill + stepwise decode (oracle for tests)."""
    cache, _ = model.init_cache(1, max_seq)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    logits, cache = model.prefill(params, toks, max_seq)
    out = []
    last = int(jnp.argmax(logits[0, -1, :model.cfg.vocab_size]))
    out.append(last)
    pos = len(prompt)
    for _ in range(max_new_tokens - 1):
        logits, cache = model.decode(
            params, cache, jnp.asarray([[last]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        last = int(jnp.argmax(logits[0, 0, :model.cfg.vocab_size]))
        out.append(last)
        pos += 1
    return np.asarray(out, np.int32)

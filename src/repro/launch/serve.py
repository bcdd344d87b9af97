"""Serving driver: open-loop Poisson load over the async serving plane.

Unlike the old submit-all-then-drain pattern, requests arrive on a Poisson
process (exponential inter-arrival gaps) while the replica decode loops run
on background threads — the arrival rate does not adapt to the system, so
queueing and latency under load are actually measured.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --requests 24 \
        --rate 4.0
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List, Optional

import numpy as np

from repro.core.monitoring import Monitor
from repro.launch.compile_cache import configure_compile_cache
from repro.serving.engine import Request, ServingEngine
from repro.serving.replica import ReplicaSet


def make_prompts(n: int, vocab_size: int, rng, lo: int = 4, hi: int = 17):
    return [rng.integers(1, vocab_size, size=int(rng.integers(lo, hi)))
            for _ in range(n)]


def make_shared_prefix_prompts(n: int, vocab_size: int, rng, *,
                               prefix_len: int = 48, lo: int = 4,
                               hi: int = 13) -> List[np.ndarray]:
    """The scientific-pipeline traffic shape: every request shares a long
    system/context head and differs only in a short payload."""
    head = rng.integers(1, vocab_size, size=prefix_len)
    return [np.concatenate([head, rng.integers(
        1, vocab_size, size=int(rng.integers(lo, hi)))]) for _ in range(n)]


def poisson_load(submit, prompts: List[np.ndarray], rate_rps: float, rng,
                 max_new_tokens: int = 12) -> List[Request]:
    """Open-loop generator: submit each prompt at its Poisson arrival time
    regardless of how the system is keeping up. Returns the Requests."""
    gaps = rng.exponential(1.0 / rate_rps, size=len(prompts)) \
        if rate_rps > 0 else np.zeros(len(prompts))
    t0 = time.perf_counter()
    arrivals = np.cumsum(gaps)
    out: List[Request] = []
    for prompt, at in zip(prompts, arrivals):
        delay = t0 + at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        out.append(submit(prompt, max_new_tokens=max_new_tokens))
    return out


def merged_poisson_load(streams, rng, max_new_tokens: int = 12) -> dict:
    """Multi-tenant open-loop load: each stream is ``(name, submit, prompts,
    rate_rps)``; arrivals are sampled per stream and merged into one
    time-ordered schedule, so tenants' requests interleave the way
    concurrent communities' traffic actually would (a hot tenant does not
    get to finish before a cold one starts). Returns name -> [Request].

    Pacing is coarse-grained: gaps below ~20ms are submitted back-to-back
    instead of slept. With busy decode threads holding the GIL, every
    ``time.sleep`` overshoots by tens of milliseconds, and at saturating
    rates that per-submission tax (not the load) would dominate measured
    walls."""
    schedule = []
    for name, submit, prompts, rate in streams:
        gaps = rng.exponential(1.0 / rate, size=len(prompts)) \
            if rate > 0 else np.zeros(len(prompts))
        arrivals = np.cumsum(gaps)
        for p, at in zip(prompts, arrivals):
            schedule.append((float(at), name, submit, p))
    schedule.sort(key=lambda s: s[0])
    out = {name: [] for name, *_ in streams}
    t0 = time.perf_counter()
    for at, name, submit, p in schedule:
        delay = t0 + at - time.perf_counter()
        if delay > 0.02:
            time.sleep(delay)
        out[name].append(submit(p, max_new_tokens=max_new_tokens))
    return out


def _percentile(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    vals = sorted(vals)
    return vals[min(len(vals) - 1, int(q * len(vals)))]


def serve_report(reqs: List[Request], wall_s: float, rs: ReplicaSet,
                 baseline: Optional[dict] = None) -> dict:
    """The serving benchmark contract: tok/s, TTFT p50, latency p95.
    ``baseline`` is a totals snapshot taken before the measured window
    (warmup / earlier traffic), subtracted so the engine counters describe
    only this load wave."""
    done = [r for r in reqs if r.done_t is not None]
    toks = sum(len(r.generated) for r in done)
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    lats = [r.latency_s for r in done if r.latency_s is not None]
    m = rs.metrics()
    base = baseline or {}

    def counter(k):
        return m["total"].get(k, 0) - base.get(k, 0)

    prompt_toks = sum(len(r.tokens) for r in done)
    out = {
        "requests": len(reqs),
        "completed": len(done),
        "tokens": toks,
        "prompt_tokens": prompt_toks,
        "wall_s": wall_s,
        "tok_per_s": toks / wall_s if wall_s > 0 else 0.0,
        # prefill throughput: prompt tokens turned into KV state per wall
        # second — prefix-cache hits raise this without touching the model
        "prefill_tok_per_s": prompt_toks / wall_s if wall_s > 0 else 0.0,
        "ttft_p50_s": _percentile(ttfts, 0.50),
        "ttft_p95_s": _percentile(ttfts, 0.95),
        "latency_p50_s": _percentile(lats, 0.50),
        "latency_p95_s": _percentile(lats, 0.95),
        "replicas": m["replicas"],
        "failovers": m["failovers"],
        "prefills": counter("prefills"),
        "prefill_requests": counter("prefill_requests"),
        "prefill_chunks": counter("prefill_chunks"),
        "prefill_chunk_batches": counter("prefill_chunk_batches"),
        "prefill_tokens": counter("prefill_tokens"),
        "prefix_hit_tokens": counter("prefix_hit_tokens"),
        "decode_steps": counter("decode_steps"),
    }
    spec_steps = counter("spec_steps")
    if spec_steps:
        proposed = counter("spec_proposed")
        out["spec_steps"] = spec_steps
        out["spec_accept_rate"] = (counter("spec_accepted") / proposed
                                   if proposed else 0.0)
        out["spec_tokens_per_step"] = counter("spec_emitted") / spec_steps
    if "prefix_cache" in m:
        out["prefix_cache"] = m["prefix_cache"]
    recorder = getattr(rs, "recorder", None)
    if recorder is not None:
        # flush so the on-disk store already covers this wave, then fold a
        # record-store summary into the serving contract
        from repro.observability import RecordStore
        recorder.flush()
        out["records"] = {**recorder.summary(),
                          **RecordStore.load(recorder.path).summary()}
    return out


def run_load(rs: ReplicaSet, prompts: List[np.ndarray], *, rate_rps: float,
             max_new_tokens: int, rng, warmup: bool = True,
             timeout_s: float = 300.0) -> dict:
    """Drive a started ReplicaSet with Poisson arrivals and report."""
    if warmup and prompts:
        # one throwaway request per distinct admission shape compiles the
        # prefill/decode kernels outside the measured window
        w = rs.submit_request(prompts[0], max_new_tokens=2)
        w.future.result(timeout=timeout_s)
        if getattr(rs, "prefix_cache", None) is not None:
            # the first request seeded the prefix cache; a second identical
            # one exercises the hit/restore path, compiling it too
            w = rs.submit_request(prompts[0], max_new_tokens=2)
            w.future.result(timeout=timeout_s)
    baseline = dict(rs.metrics()["total"])   # exclude warmup/prior traffic
    t0 = time.perf_counter()
    reqs = poisson_load(rs.submit_request, prompts, rate_rps, rng,
                        max_new_tokens)
    for r in reqs:
        r.future.result(timeout=timeout_s)
    wall = time.perf_counter() - t0
    return serve_report(reqs, wall, rs, baseline)


def build_replicaset(arch: str, *, replicas: int, slots: int, max_seq: int,
                     monitor=None, mesh=None, chunk_tokens: int = 0,
                     prefix_cache_mb: float = 0.0, speculate: int = 0,
                     draft: str = "ngram",
                     record_path: Optional[str] = None) -> ReplicaSet:
    import jax
    from repro.configs import served_config
    from repro.models.model import build_model, init_params
    from repro.serving.prefix_cache import PrefixCache
    from repro.serving.speculative import build_draft, supports_speculation

    device = mesh.devices.flat[0] if mesh is not None else jax.devices()[0]
    cfg = served_config(arch, device.platform)
    model = build_model(cfg)
    params = init_params(model, jax.random.PRNGKey(0), device)
    prefix_cache = None
    if chunk_tokens and prefix_cache_mb > 0:
        prefix_cache = PrefixCache(chunk_tokens,
                                   budget_bytes=int(prefix_cache_mb * 2**20),
                                   monitor=monitor)
    recorder = None
    if record_path:
        from repro.observability import Recorder
        recorder = Recorder(
            record_path, tenant=arch, monitor=monitor,
            meta={"arch": arch, "platform": device.platform,
                  "device_kind": device.device_kind,
                  "serving": {"replicas": replicas, "slots": slots,
                              "max_seq": max_seq,
                              "chunk_tokens": chunk_tokens,
                              "prefix_cache_mb": prefix_cache_mb,
                              "speculate": speculate, "draft": draft}})
    # skip draft construction where the engine would gate speculation off
    # (rolling/SSM/MoE archs): it would only allocate unused per-replica
    # state on every spawn; the engine still logs the fallback
    spec_supported = bool(speculate) and supports_speculation(model, max_seq)

    def factory(i: int, devices=None) -> ServingEngine:
        d = build_draft(draft, cfg, slots=slots, max_seq=max_seq,
                        devices=devices, name=f"replica{i}-draft") \
            if spec_supported else None
        return ServingEngine(model, params, slots=slots, max_seq=max_seq,
                             name=f"replica{i}", monitor=monitor,
                             devices=devices, chunk_tokens=chunk_tokens,
                             prefix_cache=prefix_cache,
                             speculate=speculate, draft=d, recorder=recorder)

    return ReplicaSet(factory, replicas=replicas, monitor=monitor, mesh=mesh,
                      prefix_cache=prefix_cache, recorder=recorder)


def run_elastic_serve(vre, *, waves: int = 2, requests_per_wave: int = 16,
                      rate_rps: float = 20.0, max_new_tokens: int = 8,
                      rng=None, timeout_s: float = 300.0,
                      force_resize: bool = False) -> dict:
    """Drive a VRE's serving plane through ``waves`` Poisson load waves,
    applying any autoscaler-requested mesh resize between waves (the safe
    point): ``elastic.resize_serving`` drains the pool, re-instantiates on
    the grown mesh, re-places replicas on disjoint slices, and the successor
    pool adopts the carried requests. Reports per-wave serving contracts and
    resize events (downtime, tok/s before/after).

    ``force_resize`` requests a default (data-axis doubling) resize before
    the inter-wave safe point when the autoscaler hasn't — benchmarks use it
    to make the elastic scenario deterministic."""
    from repro.core import elastic

    rng = rng if rng is not None else np.random.default_rng(0)
    server = vre.service("lm-server")
    rs = server.replicaset
    vocab = rs.engines[0].cfg.vocab_size
    wave_reports, resize_events = [], []
    total_reqs = total_done = 0
    for w in range(waves):
        prompts = make_prompts(requests_per_wave, vocab, rng)
        rep = run_load(rs, prompts, rate_rps=rate_rps,
                       max_new_tokens=max_new_tokens, rng=rng,
                       timeout_s=timeout_s)
        rep["wave"] = w
        rep["mesh"] = list(vre.config.mesh_shape)
        rep["placements"] = {n: [str(d) for d in devs]
                             for n, devs in rs.placements().items()}
        wave_reports.append(rep)
        total_reqs += rep["requests"]
        total_done += rep["completed"]
        if w == waves - 1:
            break
        if force_resize and vre.pending_resize is None:
            vre.request_resize()
        ev = elastic.resize_serving(vre)
        if ev is not None:
            server = vre.service("lm-server")     # rebuilt on the new mesh
            rs = server.replicaset
            if server.autoscaler is not None:
                server.autoscaler.notify_resized()
            r = ev["report"]
            resize_events.append({
                "after_wave": w,
                "old_shape": list(r.old_shape),
                "new_shape": list(r.new_shape),
                "downtime_s": ev["downtime_s"],
                "reinstantiate_s": r.reinstantiate_s,
                "carried_requests": ev["carried_requests"],
            })
    for ev in resize_events:
        w = ev["after_wave"]
        ev["tok_per_s_before"] = wave_reports[w]["tok_per_s"]
        ev["tok_per_s_after"] = wave_reports[w + 1]["tok_per_s"]
    return {
        "waves": wave_reports,
        "resizes": resize_events,
        "requests": total_reqs,
        "completed": total_done,
        "completion_rate": total_done / total_reqs if total_reqs else 1.0,
        "final_mesh": list(vre.config.mesh_shape),
    }


def validate_serving_args(args, error, zero_disables: bool = False) -> None:
    """Reject malformed serving knobs with a one-line error instead of a
    deep jax/engine traceback: a negative or zero chunk size would reach the
    engine as a "truthy" chunk config and explode inside jitted slicing; a
    negative cache budget would quietly evict everything.

    ``zero_disables`` is for subcommands whose defaults are
    enabled-by-default (``fleet``): there 0 is the explicit off switch, so
    only negatives are malformed — "omit the flag" would send the user in
    a circle back to the default."""
    off = "pass 0" if zero_disables else "omit the flag"
    bad_chunk = (lambda v: v < 0) if zero_disables else (lambda v: v <= 0)
    if args.chunk_tokens is not None and bad_chunk(args.chunk_tokens):
        error(f"--chunk-tokens must be a positive integer, got "
              f"{args.chunk_tokens} ({off} to disable chunked prefill)")
    if args.prefix_cache_mb is not None and bad_chunk(args.prefix_cache_mb):
        error(f"--prefix-cache-mb must be positive, got "
              f"{args.prefix_cache_mb} ({off} to disable the prefix cache)")
    if args.prefix_cache_mb and args.chunk_tokens is not None \
            and not args.chunk_tokens:
        error("--prefix-cache-mb requires chunked prefill "
              "(prefix entries live at chunk boundaries)")
    if args.prefix_cache_mb and args.chunk_tokens is None \
            and not zero_disables:
        error("--prefix-cache-mb requires --chunk-tokens "
              "(prefix entries live at chunk boundaries)")
    speculate = getattr(args, "speculate", None)
    if speculate is not None and bad_chunk(speculate):
        error(f"--speculate must be a positive number of draft tokens, got "
              f"{speculate} ({off} to disable speculative decoding)")
    draft = getattr(args, "draft", None)
    if draft is not None and draft not in ("model", "ngram"):
        error(f"--draft must be 'model' or 'ngram', got {draft!r}")
    if draft is not None and not speculate and not zero_disables:
        error("--draft requires --speculate "
              "(a draft only exists to propose speculative tokens)")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-9b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--rate", type=float, default=4.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunk-wise prefill in pieces of this many tokens "
                         "(omit to disable; required for prefix caching)")
    ap.add_argument("--prefix-cache-mb", type=float, default=None,
                    help="cross-request prefix-cache LRU budget in MiB "
                         "(omit to disable)")
    ap.add_argument("--speculate", type=int, default=None,
                    help="speculative decoding: draft tokens verified per "
                         "decode step (omit to disable)")
    ap.add_argument("--draft", choices=("model", "ngram"), default=None,
                    help="draft engine for --speculate: 'ngram' prompt "
                         "lookup (default) or a small 'model' transformer")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prompts share a prefix head of this many tokens "
                         "(0: independent prompts)")
    ap.add_argument("--record", default=None, metavar="PATH",
                    help="flight recorder: write one JSONL record per "
                         "request (enables per-request tracing)")
    args = ap.parse_args(argv)
    validate_serving_args(args, ap.error)
    configure_compile_cache()
    args.chunk_tokens = args.chunk_tokens or 0
    args.prefix_cache_mb = args.prefix_cache_mb or 0.0
    args.speculate = args.speculate or 0

    monitor = Monitor()
    rs = build_replicaset(args.arch, replicas=args.replicas,
                          slots=args.slots, max_seq=args.max_seq,
                          monitor=monitor, chunk_tokens=args.chunk_tokens,
                          prefix_cache_mb=args.prefix_cache_mb,
                          speculate=args.speculate,
                          draft=args.draft or "ngram",
                          record_path=args.record)
    vocab = rs.engines[0].cfg.vocab_size      # the served config
    rs.start()
    rng = np.random.default_rng(0)
    if args.shared_prefix:
        prompts = make_shared_prefix_prompts(args.requests, vocab, rng,
                                             prefix_len=args.shared_prefix)
    else:
        prompts = make_prompts(args.requests, vocab, rng)
    try:
        report = run_load(rs, prompts, rate_rps=args.rate,
                          max_new_tokens=args.max_new, rng=rng)
    finally:
        rs.stop()
    print(json.dumps(report, indent=2))
    return report


if __name__ == "__main__":
    main()

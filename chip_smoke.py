#!/usr/bin/env python3
"""Serving-plane smoke run on a TPU at published widths, in one process.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four one-chip replicas vs one replica

One chip: a ``tpu-v5e`` VRE on mesh [1, 1] serves yi-9b's one-chip cut
(every published width, 24 of 48 layers; see ``repro/configs/yi_9b.py``)
through ``VirtualResearchEnvironment`` -> ``build_server`` -> ``ReplicaSet``
-> ``ServingEngine``, as ``python -m repro.cli serve`` does: 16 seeded
requests of 64-512 prompt tokens and 32 new tokens each, 16 slots, max_seq
2048. It then checks the cache path's numbers: for 2 prompts, the logits
that prefill + decode through the slotted cache give at the last prompt
position and at 4 decoded positions against ``model.forward`` on the whole
sequence.

``--chips 4``: a VRE on mesh [4, 1] with ``replicas: "auto"`` (one replica
per chip) serves 16 equal-length prompts; the same prompts served by one
replica on one chip must give identical tokens.

Rates printed here are smoke figures from one short run, not benchmark
results. The last line of stdout is the JSON device record; the script
exits non-zero, before that line, when JAX finds no TPU or a check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "yi-9b"
SLOTS = 16
MAX_SEQ = 2048
NEW_TOKENS = 32
PROMPT_LENS = (64, 128, 256, 512)   # few values: few prefill buckets
# Served logits (bf16 weights and activations, f32 accumulation) against
# the same model's whole-sequence forward: the two differ only in the
# order and grouping of bf16 roundings (cached K/V, a 16-row decode batch,
# a padded prefill), so each compared position may differ by a few bf16
# unit roundoffs (2**-9) of its logits: one of the largest logit in max-abs
# terms, four in L2 terms. A v5e measured 1.5e-4 and 2.1e-3; a decode that
# rotates by the wrong position (one off) fails both, even on the CPU at
# reduced size (7.7e-3 and 1.3e-2).
MAX_ABS_TOL = 2.0 ** -9     # max |served - forward| / max |forward|
REL_L2_TOL = 2.0 ** -7      # ||served - forward||_2 / ||forward||_2


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(devices, count: int = 1) -> dict:
    """The device record of the last line. Exits non-zero unless JAX's
    devices are TPUs, at least ``count`` of them."""
    if not devices or devices[0].platform != "tpu":
        found = sorted({d.platform for d in devices})
        raise SystemExit(f"chip_smoke: no TPU; JAX found {found}")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devices)}")
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileLog:
    """Counts backend compiles (persistent-cache hits are not compiles)
    and their seconds, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0

        def on_duration(name, secs, **_):
            if name == self.EVENT:
                self.n += 1
                self.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def line(self) -> str:
        return f"{self.n} backend compiles, {self.seconds:.1f} s"


def tree_bytes(tree) -> int:
    import jax
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def memory(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


def make_vre(name: str, mesh_shape, replicas):
    import repro.core.services  # noqa: F401  (registers the lm-server)
    from repro.core.vre import VREConfig, VirtualResearchEnvironment
    cfg = VREConfig(name=name, mesh_shape=tuple(mesh_shape),
                    services=["lm-server"], arch=ARCH, provider="tpu-v5e",
                    workdir=tempfile.mkdtemp(prefix="chip_smoke_"),
                    extra={"replicas": replicas, "slots": SLOTS,
                           "max_seq": MAX_SEQ})
    vre = VirtualResearchEnvironment(cfg)
    vre.instantiate()
    return vre


def serving_errors(vre) -> list:
    return [e for e in vre.monitor.events()
            if e["event"] in ("prefill_error", "step_error", "failover")]


def check_served(report: dict, vre) -> None:
    errors = serving_errors(vre)
    if report["completed"] != report["requests"] or report["failovers"] \
            or errors:
        raise SystemExit(f"chip_smoke: serving failed: completed "
                         f"{report['completed']}/{report['requests']}, "
                         f"failovers {report['failovers']}, events {errors}")


def print_cut() -> None:
    from repro.configs import served_cut
    cut = served_cut(ARCH)
    c = cut.config
    log(f"config: {ARCH} d_model={c.d_model} heads={c.num_heads}q/"
        f"{c.num_kv_heads}kv x {c.head_dim} d_ff={c.d_ff} "
        f"vocab={c.vocab_size} layers={c.num_layers} dtype={c.dtype}")
    log(f"cut: reduced={json.dumps(cut.reduced)}")
    log(f"cut: deployment={cut.deployment}; chips per layer "
        f"{cut.chips_per_layer}")


def check_logits(eng, rng) -> None:
    """Prefill + decode through the engine's slotted cache against the
    model's whole-sequence forward, for 2 prompts of different lengths
    admitted together (so the prefill pads one of them)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    vocab = eng.cfg.vocab_size
    n_decode = 4
    prompts = [rng.integers(1, vocab, size=n) for n in (100, 300)]
    steps = []
    decode = eng._decode

    def recording_decode(p, cache, toks, pos):
        logits, cache = decode(p, cache, toks, pos)
        steps.append(np.asarray(logits[:, 0, :vocab], np.float32))
        return logits, cache

    eng._decode = recording_decode
    try:
        reqs = [eng.submit_request(p, max_new_tokens=n_decode + 1)
                for p in prompts]
        eng.run_until_idle()
    finally:
        eng._decode = decode
    width = max(len(p) for p in prompts) + n_decode
    seqs = np.zeros((len(prompts), width), np.int32)
    for j, (p, r) in enumerate(zip(prompts, reqs)):
        seqs[j, :len(p) + n_decode] = np.concatenate(
            [p, np.asarray(r.generated[:n_decode])])
    ref, _ = jax.jit(eng.model.forward)(eng.params, jnp.asarray(seqs))
    ref = np.asarray(ref[..., :vocab], np.float32)
    worst_abs = worst_l2 = 0.0
    for j, (p, r) in enumerate(zip(prompts, reqs)):
        for k in range(n_decode + 1):
            got = steps[k][r.slot]
            want = ref[j, len(p) - 1 + k]
            if not (np.isfinite(got).all() and np.isfinite(want).all()):
                raise SystemExit("chip_smoke: non-finite logits")
            worst_abs = max(worst_abs, float(np.abs(got - want).max()
                                             / np.abs(want).max()))
            worst_l2 = max(worst_l2, float(np.linalg.norm(got - want)
                                           / np.linalg.norm(want)))
    log(f"logits: 2 prompts x (last prompt position + {n_decode} decoded) "
        f"vs forward: max-abs/max {worst_abs:.4g} (tol {MAX_ABS_TOL}), "
        f"rel-L2 {worst_l2:.4g} (tol {REL_L2_TOL}), all finite")
    if worst_abs > MAX_ABS_TOL or worst_l2 > REL_L2_TOL:
        raise SystemExit("chip_smoke: served logits outside tolerance")


def one_chip(compiles: CompileLog) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import run_load

    print_cut()
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    vre = make_vre("chip-smoke", (1, 1), 1)
    rs = vre.service("lm-server").replicaset
    eng = rs.engines[0]
    param_bytes, cache_bytes = tree_bytes(eng.params), tree_bytes(eng.cache)
    mem = memory(dev)
    log(f"bytes: params {param_bytes} cache {cache_bytes} ({SLOTS} slots x "
        f"{MAX_SEQ}); device after instantiate {mem}; instantiate "
        f"{time.perf_counter() - t0:.1f} s")
    # params once: the first replica aliases the served-model params
    if mem["bytes_in_use"] is not None and \
            mem["bytes_in_use"] > 1.5 * param_bytes + cache_bytes:
        raise SystemExit("chip_smoke: the chip holds params more than once")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, eng.cfg.vocab_size, size=int(n))
               for n in rng.choice(PROMPT_LENS, size=16)]
    report = run_load(rs, prompts, rate_rps=0.0, max_new_tokens=NEW_TOKENS,
                      rng=rng, timeout_s=900)
    check_served(report, vre)
    log(f"served: {report['completed']}/{report['requests']} requests, "
        f"{report['tokens']} tokens, prompt lengths "
        f"{sorted(len(p) for p in prompts)}, failovers "
        f"{report['failovers']}, prefills {report['prefills']}, decode "
        f"steps {report['decode_steps']}")
    log(f"smoke figures (one short run, not a benchmark): wall "
        f"{report['wall_s']:.3f} s, {report['tok_per_s']:.1f} tok/s, ttft "
        f"p50 {report['ttft_p50_s']:.3f} s")
    vre.destroy()
    check_logits(eng, rng)
    log(f"compiles: {compiles.line()}")
    mem = memory(dev)
    log(f"memory: {mem}")
    if mem["peak_bytes_in_use"] is not None and mem["bytes_limit"] and \
            mem["peak_bytes_in_use"] >= mem["bytes_limit"]:
        raise SystemExit("chip_smoke: peak memory reached the chip's limit")


def serve_tokens(vre, prompts) -> list:
    import numpy as np
    from repro.launch.serve import poisson_load, serve_report
    rs = vre.service("lm-server").replicaset
    t0 = time.perf_counter()
    reqs = poisson_load(rs.submit_request, prompts, 0.0,
                        np.random.default_rng(0), max_new_tokens=NEW_TOKENS)
    outs = [r.future.result(timeout=900) for r in reqs]
    report = serve_report(reqs, time.perf_counter() - t0, rs)
    check_served(report, vre)
    return outs


def four_chips(compiles: CompileLog) -> None:
    import jax
    import numpy as np

    one = make_vre("chip-smoke-one", (1, 1), 1)
    rng = np.random.default_rng(0)
    vocab = one.service("lm-server").replicaset.engines[0].cfg.vocab_size
    prompts = [rng.integers(1, vocab, size=256) for _ in range(16)]
    ref = serve_tokens(one, prompts)
    one.destroy()
    del one
    gc.collect()
    log(f"one replica on one chip: {len(ref)} requests served")

    four = make_vre("chip-smoke-four", (4, 1), "auto")
    rs = four.service("lm-server").replicaset
    placements = {n: [str(d) for d in devs]
                  for n, devs in rs.placements().items()}
    log(f"placements: {json.dumps(placements)}")
    devices = {d for devs in rs.placements().values() for d in devs}
    if len(rs.engines) != 4 or len(devices) != 4 or \
            any(len(devs) != 1 for devs in placements.values()):
        raise SystemExit("chip_smoke: want 4 replicas on 4 distinct chips")
    eng = rs.engines[0]
    need = tree_bytes(eng.params) + tree_bytes(eng.cache)
    per_chip = {d.id: memory(d)["bytes_in_use"] for d in jax.devices()[:4]}
    log(f"bytes in use per chip: {per_chip} (params + cache = {need})")
    if any(b is not None and b < need for b in per_chip.values()):
        raise SystemExit("chip_smoke: a chip lacks its replica's params "
                         "and cache")
    got = serve_tokens(four, prompts)
    served = {n: m["completed"]
              for n, m in rs.metrics()["per_replica"].items()}
    four.destroy()
    log(f"four replicas: requests completed per replica {served}")
    same = sum(bool(np.array_equal(a, b)) for a, b in zip(ref, got))
    log(f"tokens identical to the one-chip run: {same}/{len(ref)}")
    log(f"compiles: {compiles.line()}")
    if same != len(ref):
        raise SystemExit("chip_smoke: four-chip tokens differ")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    import jax
    from repro.launch.compile_cache import configure_compile_cache

    device = require_tpu(jax.devices(), args.chips)
    log(f"compile cache: {configure_compile_cache()}")
    compiles = CompileLog()
    if args.chips == 4:
        four_chips(compiles)
    else:
        one_chip(compiles)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

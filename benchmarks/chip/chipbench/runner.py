"""One run of one cell, in the order the benchmark's contract sets.

1. Find the chip (or stop before any result), set the compile cache.
2. Make the weights from the seed on each of the cell's chips, build the
   served path: one replica on each chip, behind the program's router.
3. Warm every shape the mix can produce, on every replica at once; start
   the load; ramp.
4. Open the window: read the counters; with ``--trace 1`` take a profiler
   trace of a few seconds inside it. Close it: read the counters, stop the
   load, wait for what was sent, read the peak memory.
5. Free the program's state; check the window's output against the plain
   reference; compute the metrics; print.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path
from typing import Optional

from chipbench import check as check_mod
from chipbench import device as device_mod
from chipbench import profile as profile_mod
from chipbench import serving, spec
from chipbench.load import Driver, clock
from chipbench.traffic import Stream
from chipbench.window import RunRecord, counters

PROFILE_DIR = spec.CHECKOUT / ".chipbench" / "profile"
TRACE_SECONDS = 3.0         # at most; a quarter of a shorter window
DRAIN_S = 180.0             # what was sent may finish this long after close


@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool


def parse(argv=None) -> Options:
    ap = argparse.ArgumentParser(
        prog="run.py", description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    return Options(a.workload, a.seed, a.seconds, bool(a.trace))


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


def configure_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it
    (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.xla_cache``); every program is kept, however quickly it
    compiled, and none is evicted (the cells' programs are a few hundred MB
    in all)."""
    import jax
    from repro.launch.compile_cache import configure_compile_cache
    path = configure_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def serve_window(cell: spec.Cell, opts: Options, devices, t_process: float,
                 compiles: device_mod.CompileLog, *, warm: bool = True,
                 layout: spec.Layout = spec.Layout(),
                 trace_dir: Path = PROFILE_DIR) -> RunRecord:
    """Steps 2-4: serve the cell's mix through the program on the cell's
    chips, one replica on each, and measure; the program's state is freed
    on return."""
    import jax
    config, mix, dep = cell.config, cell.traffic, cell.config["deployment"]
    ref, cost = spec.family_modules(config, layout)
    used = list(devices)[:cell.chips]
    t = clock()
    # the same seed on every chip: every replica serves the same model
    params = [ref.program_params(config["model"], opts.seed, d)
              for d in used]
    jax.block_until_ready(params)
    t_weights = clock() - t
    model, rs = serving.build(config, params, used, traced=opts.trace)
    del params
    vocab = int(config["model"]["vocab_size"])
    stream = Stream(mix, opts.seed, vocab, int(dep["max_seq"]))
    t = clock()
    warmed = serving.warm(rs.engines, stream.bounds(), dep, vocab) \
        if warm else 0
    t_warm = clock() - t
    n_setup, s_setup, hits = compiles.snapshot()
    rs.start()
    driver = Driver(mix, stream, rs.submit_request)
    t_start = clock()
    driver.start(t_start)
    try:
        time.sleep(max(0.0, t_start + float(mix["ramp_s"]) - clock()))
        (c_open, r_open), t_open = counters(rs.engines), clock()
        n_open = compiles.snapshot()[0]
        setup_s = t_open - t_process
        summary = None
        if opts.trace:
            lead = 0.25 * opts.seconds
            time.sleep(lead)
            span = min(TRACE_SECONDS, 0.25 * opts.seconds)
            xplane = profile_mod.capture(str(trace_dir), span)
        time.sleep(max(0.0, t_open + opts.seconds - clock()))
        (c_close, r_close), t_close = counters(rs.engines), clock()
        n_close = compiles.snapshot()[0]
        driver.stop()
        drained = driver.drain(DRAIN_S)
    finally:
        driver.stop()
        rs.stop()
    if opts.trace:
        summary = profile_mod.summarize(profile_mod.load_events(xplane))
    dev = device_mod.record(devices, used)
    log(f"set-up {setup_s:.3f} s: weights {t_weights:.3f} s, warm-up "
        f"{t_warm:.3f} s ({warmed} requests), ramp {mix['ramp_s']} s; "
        f"{n_setup} programs made ready in {s_setup:.1f} s: "
        f"{n_setup - hits} compiled (cold), {hits} loaded from the cache "
        f"(warm)")
    log(f"compiles inside the window: {n_close - n_open}")
    log(f"window {t_close - t_open:.3f} s; {len(driver.sent)} requests "
        f"sent over {len(used)} replicas; all finished after close: "
        f"{drained}")
    log(f"device: {json.dumps(dev)}")
    record = RunRecord(
        cell=cell.name, seconds=opts.seconds, t_open=t_open,
        t_close=t_close, setup_s=setup_s, counters_open=c_open,
        counters_close=c_close, sent=driver.sent, model=config["model"],
        deployment=dep, cost=cost,
        peaks=device_mod.peaks(devices[0].device_kind)
        if devices[0].platform == "tpu" else None,
        device=dev, traced=opts.trace, profile=summary, chips=len(used),
        replicas_open=r_open, replicas_close=r_close)
    del rs, model, driver
    gc.collect()
    log(f"device bytes still held after the served path was freed: "
        f"{sum(x.nbytes for x in jax.live_arrays())}")
    return record


def metrics(cell: spec.Cell, record: RunRecord, layout: spec.Layout,
            traced: bool) -> dict:
    out = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.load_module(layout.metrics, m["name"]).read(record)
        if value is None:
            log(f"{m['name']}: nothing to read in this run; left out")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(rec: RunRecord, checks: dict, metric_values: dict) -> dict:
    due = rec.due_in_window()
    line = {"correct": check_mod.compare(checks),
            "attempted": len(due),
            "failed": sum(s.failed for s in due),
            "metrics": metric_values,
            "device": dict(rec.device)}
    if rec.traced and rec.profile is not None \
            and rec.profile["busy_s"] is not None:
        line["device"]["busy_s"] = rec.profile["busy_s"]
        line["device"]["window_s"] = rec.profile["window_s"]
        line["breakdown"] = {"device_ops": rec.profile["device_ops"],
                             "idle_gaps": rec.profile["idle_gaps"]}
    line["checks"] = {k: [c["value"], c.get("max", c.get("min"))]
                      for k, c in checks.items()}
    return line


def run(argv=None, *, layout: spec.Layout = spec.Layout(),
        bench: Optional[dict] = None, need_chip: bool = True,
        persistent_cache: bool = True, t_process: Optional[float] = None,
        trace_dir: Path = PROFILE_DIR) -> dict:
    """The whole run; returns the result line (also printed last)."""
    t_process = clock() if t_process is None else t_process
    opts = parse(argv)
    bench = spec.load_benchmark() if bench is None else bench
    cell = spec.resolve(bench, opts.workload, layout)
    import jax
    devices = jax.devices()
    if need_chip:
        device_mod.require_chip(devices, cell.chips)
    if persistent_cache:
        configure_cache()
    compiles = device_mod.CompileLog()
    record = serve_window(cell, opts, devices, t_process, compiles,
                          layout=layout, trace_dir=trace_dir)
    ref, _ = spec.family_modules(cell.config, layout)
    t = clock()
    served = int(cell.traffic["check_served_tokens"])
    checks = check_mod.checks(record, ref, cell.config, opts.seed,
                              served)["f32"]
    log(f"reference check {clock() - t:.3f} s")
    values = metrics(cell, record, layout, opts.trace)
    line = result_line(record, checks, values)
    for k, c in checks.items():
        bound = f"max {c['max']}" if "max" in c else f"min {c['min']}"
        print(f"check {k}: {c['value']} ({bound})", file=sys.stderr,
              flush=True)
    print(json.dumps(line), flush=True)
    return line

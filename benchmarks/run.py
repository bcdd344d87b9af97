"""Benchmark harness: one entry per paper figure (+ roofline + serving).
Prints ``name,us_per_call,derived`` CSV per the harness contract and writes
full JSON to experiments/bench/.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

OUT = Path(__file__).resolve().parents[1] / "experiments" / "bench"


def _run(name, fn, derived_fn, fast):
    t0 = time.perf_counter()
    result = fn(fast=fast)
    dt = time.perf_counter() - t0
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(result, indent=2))
    print(f"{name},{dt * 1e6:.0f},{derived_fn(result)}", flush=True)
    return result


def main() -> None:
    fast = "--full" not in sys.argv
    from benchmarks import (bench_serving, fig5_tool_speedup, fig6_wse,
                            fig7_deployment, fig8_backends, roofline)

    _run("fig5_tool_speedup", fig5_tool_speedup.main,
         lambda r: "max_speedup=%.1f" % max(
             max(v["speedup"].values()) for v in r.values()), fast)
    _run("fig6_wse", fig6_wse.main,
         lambda r: "wse_at_4x=%.3f" % r["wse"][40], fast)
    _run("fig7_deployment", fig7_deployment.main,
         lambda r: "kubenow_vs_kubespray_at_max=%.1fx" % r["speedup_at_max"],
         fast)
    _run("fig8_backends", fig8_backends.main,
         lambda r: "aws_64_over_gcp_64=%.2f" % (
             r["aws"][-1] / r["gcp"][-1]), fast)
    _run("roofline", roofline.main,
         lambda r: "cells=%d dominant=%s" % (
             r["cells"], max(r["dominant_histogram"],
                             key=r["dominant_histogram"].get)), fast)
    _run("serving_throughput", bench_serving.main,
         lambda r: "tok_per_s=%.1f" % r["tok_per_s"], fast)


if __name__ == "__main__":
    main()

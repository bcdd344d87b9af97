import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + \
    os.environ.get("XLA_FLAGS", "")
# ^ MUST be the first statements: jax locks the device count on first init.
#   The dry-run (and ONLY the dry-run) sees 512 placeholder devices so the
#   production meshes (16x16 single-pod, 2x16x16 multi-pod) can be built.

"""Multi-pod dry-run: ``.lower().compile()`` every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, and unsupported collectives all fail here.
Per cell we record memory_analysis (fits-HBM proof), cost_analysis, and the
trip-count-weighted HLO analysis (FLOPs / HBM bytes / collective bytes) that
feeds EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch yi-9b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every runnable cell, both meshes
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs.base import SHAPES, get_config, all_cells  # noqa: E402
from repro.launch import hlo_analysis, mesh as mesh_lib, specs  # noqa: E402
from repro.launch.compile_cache import CHECKOUT, configure_compile_cache  # noqa: E402

OUT_DIR = CHECKOUT / "experiments" / "dryrun"


def build_step(cfg, shape, mesh, policy, parallel, model, aux,
               microbatch_budget=4e9):
    """Returns (jitted fn, abstract args)."""
    from repro.launch.specs import input_specs
    if shape.kind == "train":
        from repro.optim.adamw import OptimizerConfig
        from repro.training.train_step import (TrainStepConfig,
                                               make_train_step,
                                               pick_microbatches)
        dp = 1
        for a in parallel.batch_axes:
            dp *= mesh.shape[a]
        mb = pick_microbatches(cfg, shape, dp, microbatch_budget)
        opt_cfg = OptimizerConfig(moment_dtype=aux["moment_dtype"],
                                  grad_accum_dtype=(
                                      "bfloat16" if (aux["moment_dtype"] !=
                                      "float32" or aux.get("grad_bf16"))
                                      else "float32"))
        step = make_train_step(model, cfg, opt_cfg,
                               TrainStepConfig(microbatches=mb))
        fn = jax.jit(step, out_shardings=(aux["state_sh"], None),
                     donate_argnums=(0,))
        return fn, {"microbatches": mb}
    if shape.kind == "prefill":
        def prefill(params, inputs):
            return model.prefill(params, inputs, shape.seq_len)
        fn = jax.jit(prefill)
        return fn, {}
    # decode
    def decode(params, caches, inputs, pos):
        return model.decode(params, caches, inputs, pos)
    fn = jax.jit(decode, out_shardings=(None, aux["cache_sh"]),
                 donate_argnums=(1,))
    return fn, {}


def _apply_variant(cfg, variant: str):
    """Variant tokens (combine with '+'): fusedattn (Pallas-kernel-semantics
    attention lowering), ssdproxy (idem for SSD), mb8/mb4 (bigger microbatch
    residual budget -> fewer weight regathers), gradbf16 (bf16 grad accum),
    int8opt (8-bit Adam moments), mesh64/mesh32 (right-sized small mesh)."""
    import dataclasses
    tokens = set(variant.split("+")) if variant else set()
    overrides = {}
    if "fusedattn" in tokens:
        overrides["attn_impl"] = "fused_proxy"
    if "ssdproxy" in tokens:
        overrides["ssd_impl"] = "fused_proxy"
    cfg = dataclasses.replace(cfg, **overrides) if overrides else cfg
    knobs = {
        "microbatch_budget": 12e9 if "mb8" in tokens else
                             24e9 if "mb4" in tokens else
                             6e9 if "mbB6" in tokens else 4e9,
        "grad_bf16": "gradbf16" in tokens,
        "int8opt": "int8opt" in tokens,
        "mesh_override": (4, 16) if "mesh64" in tokens else
                         (2, 16) if "mesh32" in tokens else None,
    }
    return cfg, knobs


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             save_hlo: bool = False, variant: str = "baseline") -> dict:
    from repro.models.model import build_model
    t0 = time.time()
    cfg = get_config(arch)
    cfg, knobs = _apply_variant(cfg, "" if variant == "baseline" else variant)
    shape = SHAPES[shape_name]
    if knobs["mesh_override"]:
        import numpy as np
        from jax.sharding import Mesh
        ms = knobs["mesh_override"]
        mesh = Mesh(np.array(jax.devices()[:ms[0] * ms[1]]).reshape(ms),
                    ("data", "model"))
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=(mesh_kind == "multi"))
    policy, parallel = specs.make_policy(cfg, shape, mesh)
    model = build_model(cfg, mesh, parallel, policy)
    args, aux = specs.input_specs(cfg, shape, policy, model)
    if shape.kind == "train" and knobs["int8opt"]:
        from repro.launch.specs import abstract_opt_state, abstract_params
        params_sds, axes, params_sh = abstract_params(model, policy)
        opt_sds, opt_sh = abstract_opt_state(params_sds, axes, policy, "int8")
        args = ({"params": params_sds, "opt": opt_sds}, args[1])
        aux["state_sh"] = {"params": params_sh, "opt": opt_sh}
        aux["moment_dtype"] = "int8"
    if shape.kind == "train" and knobs["grad_bf16"]:
        aux["grad_bf16"] = True
    fn, extra = build_step(cfg, shape, mesh, policy, parallel, model, aux,
                           microbatch_budget=knobs["microbatch_budget"])

    t1 = time.time()
    lowered = fn.lower(*args)
    t2 = time.time()
    compiled = lowered.compile()
    t3 = time.time()

    ma = compiled.memory_analysis()
    print(ma)
    ca = compiled.cost_analysis()
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()
    stats = hlo_analysis.analyze_module(hlo)

    chips = mesh.devices.size
    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    if shape.kind == "train":
        model_flops_global = 6.0 * n_active * tokens
    else:
        model_flops_global = 2.0 * n_active * tokens
    model_flops_dev = model_flops_global / chips

    # the roofline is the production chip's (the placeholder CPU devices
    # this compiles on have no peaks of their own)
    peaks = mesh_lib.chip_peaks(mesh_lib.PRODUCTION_DEVICE_KIND)
    compute_s = stats.flops / peaks.flops_bf16
    memory_s = (stats.hbm_bytes_tpu or stats.hbm_bytes) / peaks.hbm_bw
    coll_s = stats.coll_wire_bytes / peaks.ici_link_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": coll_s}
    dominant = max(terms, key=terms.get)
    hbm_per_dev = (ma.argument_size_in_bytes + ma.output_size_in_bytes +
                   ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    # TPU-adjusted: the CPU backend upcasts bf16 dot operands to f32 and
    # hoists whole saved-stack converts out of loops; those buffers cannot
    # exist on the TPU target (MXU consumes bf16 natively).
    hbm_adjusted = hbm_per_dev - stats.upcast_buffer_bytes

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant,
        "chips": chips,
        "attn_mode": policy.mode,
        "sharding_fallbacks": [list(map(str, f)) for f in policy.fallbacks],
        "timings_s": {"build": t1 - t0, "lower": t2 - t1, "compile": t3 - t2},
        "memory_analysis": {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "peak_hbm_per_device_bytes": hbm_per_dev,
            "cpu_upcast_buffer_bytes": stats.upcast_buffer_bytes,
            "peak_hbm_tpu_adjusted_bytes": hbm_adjusted,
            "fits_16gb": bool(hbm_adjusted < peaks.hbm_bytes),
            "fits_16gb_raw_cpu": bool(hbm_per_dev < peaks.hbm_bytes),
        },
        "cost_analysis_raw": {"flops": ca.get("flops"),
                              "bytes_accessed": ca.get("bytes accessed")},
        "memory_s_cpu_raw": stats.hbm_bytes / peaks.hbm_bw,
        "hlo_stats": stats.to_json(),
        "roofline": {
            **terms,
            "dominant": dominant,
            "model_flops_global_6ND": model_flops_global,
            "model_flops_per_device": model_flops_dev,
            "hlo_flops_per_device": stats.flops,
            "useful_flops_ratio": (model_flops_dev / stats.flops
                                   if stats.flops else None),
            "roofline_fraction": (model_flops_dev / peaks.flops_bf16
                                  / max(compute_s, memory_s, coll_s)
                                  if max(compute_s, memory_s, coll_s) else None),
        },
        **extra,
    }
    if save_hlo:
        import gzip
        hlo_path = OUT_DIR / f"{arch}__{shape_name}__{mesh_kind}__{variant}.hlo.gz"
        with gzip.open(hlo_path, "wt") as f:
            f.write(hlo)
        result["hlo_path"] = str(hlo_path)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every runnable cell x both meshes in subprocesses")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args()
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        cells = all_cells()
        failures = []
        for arch, shape in cells:
            for mesh_kind in ("single", "multi"):
                tag = f"{arch}__{shape}__{mesh_kind}"
                dest = out_dir / f"{tag}.json"
                if dest.exists():
                    print(f"[skip] {tag}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                       "--out", str(out_dir)]
                print(f"[run ] {tag}", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True)
                if r.returncode != 0:
                    failures.append(tag)
                    (out_dir / f"{tag}.err").write_text(
                        r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                    print(f"[FAIL] {tag}")
        print(f"done; {len(failures)} failures: {failures}")
        sys.exit(1 if failures else 0)

    tag = f"{args.arch}__{args.shape}__{args.mesh}"
    if args.variant != "baseline":
        tag += f"__{args.variant}"
    try:
        result = run_cell(args.arch, args.shape, args.mesh,
                          save_hlo=args.save_hlo, variant=args.variant)
    except Exception:
        traceback.print_exc()
        sys.exit(1)
    dest = Path(args.out) / f"{tag}.json"
    dest.write_text(json.dumps(result, indent=2))
    r = result["roofline"]
    print(f"[ok] {tag}: dominant={r['dominant']} "
          f"compute={r['compute_s']:.4f}s memory={result['roofline']['memory_s']:.4f}s "
          f"coll={r['collective_s']:.4f}s fit16gb={result['memory_analysis']['fits_16gb']} "
          f"(compile {result['timings_s']['compile']:.1f}s)")


if __name__ == "__main__":
    main()

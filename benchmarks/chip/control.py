#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, for many seeds in one
process (the benchmark's own runs never run this).

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 10 [--fault altered_token|unchanged_cache] \
        [--out chiprun_out/control.json]

For each seed it serves a window of the cell's own mix through the program,
exactly as ``run.py`` does, and judges it with the harness's own check
(``chipbench.check.checks`` and ``compare``, the cell's limit) twice:

- ``program``: the served tokens, as ``run.py`` judges them;
- ``control``: in their place, the tokens that the reference computed in
  fp8 (``reference/<family>.py``, mode "fp8") puts first at the same
  positions.

Each gives the widest gap by which a judged token's float32 reference logit
lies below the best, and whether the run would be ``correct``. The limit lies
between the largest ``program`` reading over a dozen seeds or more and the
smallest ``control`` reading over three or more. With ``--fault`` the
program runs with that fault planted (``chipbench/faults.py``) and only the
served tokens are judged.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import check, device, faults, runner, spec  # noqa: E402
from chipbench.load import clock  # noqa: E402


def readings(cell, seeds, seconds, devices, *, modes=("f32", "fp8"),
             layout=spec.Layout(), log=runner.log) -> list:
    """Per seed and mode: the widest gap, and the check's verdict."""
    compiles = device.CompileLog()
    ref, _ = spec.family_modules(cell.config, layout)
    names = {"f32": "program", "fp8": "control"}
    out = []
    for i, seed in enumerate(seeds):
        opts = runner.Options(cell.name, seed, seconds, False)
        record = runner.serve_window(cell, opts, devices, clock(), compiles,
                                     warm=(i == 0), layout=layout)
        t = clock()
        judged = check.checks(record, ref, cell.config, seed,
                              int(cell.traffic["check_served_tokens"]),
                              modes=modes)
        row = {"seed": seed, "reference_s": clock() - t}
        for mode, c in judged.items():
            row[names[mode]] = c.get("logit_gap", {}).get("value")
            row[names[mode] + "_correct"] = check.compare(c)
        row["checks"] = {k: [c["value"], c.get("max", c.get("min"))]
                         for k, c in judged["f32"].items()}
        log(f"control reading: {json.dumps(row)}")
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    ap.add_argument("--out")
    a = ap.parse_args(argv)
    cell = spec.resolve(spec.load_benchmark(), a.workload)
    import jax
    devices = jax.devices()
    device.require_chip(devices, cell.chips)
    runner.configure_cache()
    if a.fault:
        faults.FAULTS[a.fault](setattr)
    rows = readings(cell, [int(s) for s in a.seeds.split(",")], a.seconds,
                    devices, modes=("f32",) if a.fault else ("f32", "fp8"))
    summary = {"workload": a.workload, "fault": a.fault, "rows": rows,
               "program_max": max((r["program"] for r in rows
                                   if r["program"] is not None),
                                  default=None),
               "program_all_correct": all(r["program_correct"]
                                          for r in rows),
               "device": device.record(devices, devices[:1])}
    if not a.fault:
        summary["control_min"] = min(r["control"] for r in rows)
        summary["control_any_correct"] = any(r["control_correct"]
                                             for r in rows)
    if a.out:
        Path(a.out).parent.mkdir(parents=True, exist_ok=True)
        Path(a.out).write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

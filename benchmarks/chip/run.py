#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, before any result, when JAX finds no TPU or fewer chips than
the cell asks for. Otherwise the last line of stdout is the JSON result:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; ``checks`` comes last. Logs and the compared numbers go to
stderr. See ``README.md`` beside this file.
"""
import time

T_PROCESS = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench.runner import run  # noqa: E402

if __name__ == "__main__":
    run(t_process=T_PROCESS)

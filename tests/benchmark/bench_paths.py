"""Where the chip benchmark's files are, and the tiny cells its tests run.

Not a ``conftest.py``: the suite's own ``tests/conftest.py`` is imported by
name (``from conftest import run_devices``), and a second one would shadow
it. Test files import the fixtures they use from here."""
import sys
from pathlib import Path

import pytest

BENCH_DIR = Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
DATA = Path(__file__).resolve().parent / "data"
TINY_CELL = "tiny-dense.tiny_mix"

if str(BENCH_DIR) not in sys.path:
    sys.path.insert(0, str(BENCH_DIR))

from chipbench import spec  # noqa: E402


@pytest.fixture(scope="session")
def tiny_layout():
    return spec.Layout(configs=DATA / "configs", traffic=DATA / "traffic")


@pytest.fixture(scope="session")
def tiny_bench():
    """The real benchmark's metrics over the tiny cell."""
    real = spec.load_benchmark()

    def every_cell(ms):
        return [{k: v for k, v in m.items() if k != "workloads"} for m in ms]

    return {**real,
            "configs": [{"name": "tiny-dense", "source": "test",
                         "file": "tests/benchmark/data/configs/"
                                 "tiny-dense.json",
                         "reduced": [], "why": "test"}],
            "workloads": [{"name": TINY_CELL, "config": "tiny-dense",
                           "traffic": "tiny_mix", "chips": 1,
                           "why": "test"}],
            "end_to_end": every_cell(real["end_to_end"]),
            "per_layer": every_cell(real["per_layer"])}

"""The readers of the engine's own counters: ``prefill_token_fill`` and
``host_gap_share`` on hand-made runs, and both in a whole traced run of the
tiny cell."""
import pytest

from bench_paths import BENCH_DIR, TINY_CELL
from bench_paths import tiny_bench, tiny_layout  # noqa: F401

from chipbench import runner, spec
from chipbench.window import RunRecord


def read(name, run):
    return spec.load_module(BENCH_DIR / "metrics", name).read(run)


def run_of(**counters):
    """A 10 s window over which each named counter rose by its value."""
    return RunRecord(
        cell="c", seconds=10.0, t_open=100.0, t_close=110.0, setup_s=1.0,
        counters_open={k: 1000 for k in counters},
        counters_close={k: 1000 + v for k, v in counters.items()},
        sent=[], model={}, deployment={"slots": 4}, cost=None, peaks=None,
        device={})


def test_prefill_token_fill_exact():
    run = run_of(prefill_positions_computed=4 * 512 + 512,
                 prefill_positions_real=100 + 412)
    assert read("prefill_token_fill", run) == pytest.approx(
        100.0 * 512 / 2560)
    full = run_of(prefill_positions_computed=300, prefill_positions_real=300)
    assert read("prefill_token_fill", full) == pytest.approx(100.0)


@pytest.mark.parametrize("counters", [
    {"prefill_positions_computed": 0, "prefill_positions_real": 0},
    {},                     # a program that does not count positions
    {"tokens": 500, "decode_steps": 40},
])
def test_prefill_token_fill_none_without_prefill(counters):
    assert read("prefill_token_fill", run_of(**counters)) is None


def test_host_gap_share_exact():
    run = run_of(host_gap_s=0.25, decode_steps=340)
    assert read("host_gap_share", run) == pytest.approx(100.0 * 0.25 / 10.0)
    assert read("host_gap_share", run_of(host_gap_s=0.0,
                                         decode_steps=1)) == 0.0


@pytest.mark.parametrize("counters", [
    {"host_gap_s": 0.0, "decode_steps": 0},
    {"host_gap_s": 0.1},    # a gap but no decode step in the window
    {"decode_steps": 340},  # a program that does not count host gaps
])
def test_host_gap_share_none_without_decode(counters):
    assert read("host_gap_share", run_of(**counters)) is None


def test_traced_run_reports_program_counters(tiny_bench, tiny_layout,
                                             tmp_path):
    line = runner.run(
        ["--workload", TINY_CELL, "--seed", str(2 ** 31 + 5),
         "--seconds", "1.5", "--trace", "1"],
        layout=tiny_layout, bench=tiny_bench, need_chip=False,
        persistent_cache=False, trace_dir=tmp_path / "prof")
    assert line["correct"] is True
    fill = line["metrics"]["prefill_token_fill"]
    gap = line["metrics"]["host_gap_share"]
    assert fill["unit"] == gap["unit"] == "%"
    assert 0 < fill["value"] <= 100
    assert 0 < gap["value"] < 100

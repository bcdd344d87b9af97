"""A whole run of the harness on the CPU at a tiny size, with the look for
a chip skipped: the result line, the traced run, and a run without a chip.
"""
import json

import pytest

from bench_paths import TINY_CELL, tiny_bench, tiny_layout  # noqa: F401

from chipbench import device, runner

ARGS = ["--workload", TINY_CELL, "--seed", str(2 ** 32 + 77),
        "--seconds", "1.5"]


def run(tiny_bench, tiny_layout, tmp_path, trace=0):
    return runner.run(ARGS + ["--trace", str(trace)], layout=tiny_layout,
                      bench=tiny_bench, need_chip=False,
                      persistent_cache=False, trace_dir=tmp_path / "prof")


def test_a_sound_run_is_correct_and_prints_its_line(tiny_bench, tiny_layout,
                                                    tmp_path, capsys):
    line = run(tiny_bench, tiny_layout, tmp_path)
    out, err = capsys.readouterr()
    assert json.loads(out.strip().splitlines()[-1]) == line
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 10
    assert set(line["metrics"]) == {"setup_s", "out_tok_s", "tpot_p95_ms",
                                    "ttft_p90_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    # the compared numbers are the last lines on stderr
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert all(t.startswith("check ") for t in tail)
    assert "compiles inside the window: 0" in err


def test_traced_run_reports_per_layer_metrics(tiny_bench, tiny_layout,
                                              tmp_path):
    line = run(tiny_bench, tiny_layout, tmp_path, trace=1)
    assert line["correct"] is True
    names = set(line["metrics"])
    # the CPU has no TPU trace and no published peaks: those are left out
    assert {"batch_occupancy", "decode_step_ms", "queue_wait_p90_s",
            "client_late_p99_ms"} <= names
    assert not {"mfu", "decode_bw_share", "device_idle_share"} & names


def test_no_chip_no_result(tiny_bench, tiny_layout, tmp_path, capsys):
    with pytest.raises(SystemExit) as e:
        runner.run(ARGS + ["--trace", "0"], layout=tiny_layout,
                   bench=tiny_bench, persistent_cache=False)
    assert isinstance(e.value, device.NoChip) and e.value.code != 0
    assert capsys.readouterr().out == ""

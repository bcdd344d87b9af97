"""The trace reduction: busy union, idle share, top operations and the
longest idle gaps named by what the host was doing, on hand-made events
and on a small trace the harness recorded on a TPU v5e."""
import gzip
import json

import pytest

from bench_paths import DATA

from chipbench import profile

DEV = "/device:TPU:0"


def ev(plane, line, name, start, end):
    return {"plane": plane, "line": line, "name": name, "start": start,
            "end": end}


def test_union_merges_and_clips():
    assert profile.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [
        [0, 3], [5, 8]]
    assert profile.clip([[0, 3], [5, 8]], 2, 6) == [[2, 3], [5, 6]]


def test_short_names():
    assert profile.short_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %a), kind=kLoop") == \
        "fusion.3"
    assert profile.short_name("jit__lambda(1061150408)") == "jit__lambda"


def test_self_time_takes_nested_operations_out():
    ops = [ev(DEV, "XLA Ops", "%while.1 = () while()", 0, 100),
           ev(DEV, "XLA Ops", "%fusion.2 = f32[] fusion()", 10, 40),
           ev(DEV, "XLA Ops", "%fusion.3 = f32[] fusion()", 50, 90),
           ev(DEV, "XLA Ops", "%copy.4 = f32[] copy()", 120, 130)]
    own = {e["name"].split(" ")[0]: t
           for e, t in profile.self_times(ops, 0, 125)}
    assert own == {"%while.1": 30, "%fusion.2": 30, "%fusion.3": 40,
                   "%copy.4": 5}


def test_summary_by_hand():
    events = [
        ev("/host:CPU", "python3", profile.WINDOW, 0, 1000),
        ev(DEV, "XLA Modules", "jit_step(12)", 100, 400),
        ev(DEV, "XLA Ops", "%dot.1 = f32[] dot()", 100, 300),
        ev(DEV, "XLA Ops", "%add.2 = f32[] add()", 250, 400),   # overlaps
        ev(DEV, "XLA Modules", "jit_argmax(9)", 700, 750),
        ev(DEV, "XLA Ops", "%reduce.7 = s32[] reduce()", 700, 750),
        ev(DEV, "XLA Ops", "%late = f32[] add()", 990, 1200),   # clipped
        ev("/host:CPU", "python3", "np.asarray(jax.Array)", 420, 690),
        ev("/host:CPU", "main/1", "PjitFunction(step)", 0, 90),
    ]
    s = profile.summarize(events)
    # busy: [100, 400) + [700, 750) + [990, 1000) = 360 of 1000 ns
    assert s["busy_s"] == pytest.approx(360e-9)
    assert s["window_s"] == pytest.approx(1000e-9)
    # add.2 overlaps dot.1's last 50 ns: that time is add.2's alone
    assert s["device_ops"][0] == ["jit_step:dot.1", pytest.approx(150e-9)]
    assert sum(v for _, v in s["device_ops"]) == pytest.approx(s["busy_s"])
    assert [o[0] for o in s["device_ops"]] == [
        "jit_step:dot.1", "jit_step:add.2", "jit_argmax:reduce.7", "late"]
    # gaps: [400, 700) 300, [750, 990) 240, [0, 100) 100
    assert s["idle_gaps"] == [
        ["np.asarray(jax.Array)", pytest.approx(300e-9)],
        [profile.NO_HOST, pytest.approx(240e-9)],
        ["PjitFunction(step)", pytest.approx(100e-9)]]


def test_no_device_operation_reads_nothing():
    s = profile.summarize([ev("/host:CPU", "python3", profile.WINDOW, 0, 10)])
    assert s["busy_s"] is None


def test_recorded_chip_trace():
    with gzip.open(DATA / "trace_events.json.gz", "rt") as f:
        events = json.load(f)
    s = profile.summarize(events)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert 0 < len(s["device_ops"]) <= 10 and 0 < len(s["idle_gaps"]) <= 10
    ops = [v for _, v in s["device_ops"]]
    gaps = [v for _, v in s["idle_gaps"]]
    assert ops == sorted(ops, reverse=True) and gaps == sorted(gaps,
                                                               reverse=True)
    # self times add up to the busy time, each moment counted once
    assert sum(ops) <= s["busy_s"] * (1 + 1e-9)
    assert all(":" in name for name, _ in s["device_ops"])

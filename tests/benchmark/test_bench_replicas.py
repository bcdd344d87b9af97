"""A cell on several chips: one replica on each, behind the program's
router. The readers that divide by chips or compare replicas, the check's
sample of every replica, and, on four forced host devices in a process of
their own, the served path built and run end to end: a four-replica open
loop, a one-chip cell beside it, and a fault on one replica."""
import json
import types

import pytest

from bench_paths import (BENCH_DIR, DATA,  # noqa: F401
                         tiny_bench, tiny_layout)
from conftest import run_devices

from chipbench import check, device, spec
from chipbench.load import Sent
from chipbench.window import RunRecord

COST = spec.load_module(BENCH_DIR / "cost", "dense_gqa")
MODEL = json.loads((BENCH_DIR / "configs" / "yi-9b-pp2.json").read_text())[
    "model"]


def read(name, run):
    return spec.load_module(BENCH_DIR / "metrics", name).read(run)


def run_of(per_replica, sent=(), chips=None):
    """A 10 s window over which replica i's counters rose by
    ``per_replica[i]``."""
    opened = [{k: 100 for k in c} for c in per_replica]
    closed = [{k: 100 + v for k, v in c.items()} for c in per_replica]
    total = {}
    for c in closed:
        for k, v in c.items():
            total[k] = total.get(k, 0) + v
    return RunRecord(
        cell="c", seconds=10.0, t_open=100.0, t_close=110.0, setup_s=1.0,
        counters_open={k: 100 * len(per_replica) for k in total},
        counters_close=total, sent=list(sent), model=MODEL,
        deployment={"slots": 16}, cost=COST,
        peaks=device.peaks("TPU v5 lite"), device={},
        chips=len(per_replica) if chips is None else chips,
        replicas_open=opened, replicas_close=closed)


def test_route_imbalance_from_per_replica_counters():
    even = run_of([{"tokens": 500}] * 4)
    assert even.count_per_replica("tokens") == [500] * 4
    assert read("route_imbalance", even) == 0.0
    # mean 400, busiest 700: 75% above the mean
    skew = run_of([{"tokens": t} for t in (700, 300, 300, 300)])
    assert read("route_imbalance", skew) == pytest.approx(75.0)
    # one replica routes nothing; an idle window has no shares
    assert read("route_imbalance", run_of([{"tokens": 900}])) is None
    assert read("route_imbalance", run_of([{"tokens": 0}] * 4)) is None


def request(prompt, first, done, n, replica=0):
    r = types.SimpleNamespace(first_token_t=first, done_t=done,
                              generated=[1] * n, replica=replica,
                              future=None)
    return Sent(index=0, client=-1, due=first - 0.1, prompt=[1] * prompt,
                max_new=n, submit=first - 0.1, request=r)


@pytest.mark.parametrize("name", ["mfu", "decode_bw_share"])
def test_shares_are_of_every_chip_used(name):
    """Four chips doing four times one chip's work read the same share;
    one chip doing that work reads four times it."""
    one = run_of([{"decode_steps": 50}],
                 sent=[request(300, 101.0, 109.0, 33)])
    four = run_of([{"decode_steps": 50}] * 4,
                  sent=[request(300, 101.0, 109.0, 33, replica=i)
                        for i in range(4)])
    crowded = run_of([{"decode_steps": 200}], sent=four.sent)
    assert read(name, four) == pytest.approx(read(name, one))
    assert read(name, crowded) == pytest.approx(4 * read(name, one))


def test_decode_step_is_per_replica():
    """Four replicas making 50 steps each in 10 s step every 200 ms, as
    one does; one replica making all 200 steps steps every 50 ms."""
    one = run_of([{"decode_steps": 50}])
    four = run_of([{"decode_steps": 50}] * 4)
    crowded = run_of([{"decode_steps": 200}])
    assert read("decode_step_ms", one) == pytest.approx(200.0)
    assert read("decode_step_ms", four) == pytest.approx(200.0)
    assert read("decode_step_ms", crowded) == pytest.approx(50.0)
    assert read("decode_step_ms", run_of([{"decode_steps": 0}] * 4)) is None


def test_host_gap_share_is_averaged_over_replicas():
    one = run_of([{"host_gap_s": 0.5, "decode_steps": 40}])
    four = run_of([{"host_gap_s": 0.5, "decode_steps": 40}] * 4)
    assert read("host_gap_share", one) == pytest.approx(5.0)
    assert read("host_gap_share", four) == pytest.approx(5.0)


def test_fewer_chips_than_the_cell_asks_for_no_result(monkeypatch, capsys,
                                                      tiny_bench,
                                                      tiny_layout):
    """A four-chip cell on a host that shows one TPU chip stops before it
    serves anything, with a non-zero code and no result line."""
    import jax
    from chipbench import runner
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [types.SimpleNamespace(platform="tpu")])
    cell = "tiny-dense.tiny_burst.x4"
    bench = {**tiny_bench, "workloads": tiny_bench["workloads"] + [
        {"name": cell, "config": "tiny-dense", "traffic": "tiny_burst",
         "chips": 4, "why": "test"}]}
    with pytest.raises(device.NoChip) as e:
        runner.run(["--workload", cell, "--seed", str(2 ** 33 + 3),
                    "--seconds", "1", "--trace", "0"],
                   layout=tiny_layout, bench=bench)
    assert e.value.code != 0 and "needs 4 TPU chips" in str(e.value)
    assert capsys.readouterr().out == ""


def test_sample_holds_a_request_of_every_replica():
    """However few tokens the check asks for, each replica's work is in
    the sample; with one replica the sample is what it always was."""
    sent = [request(10 + i, 101.0, 102.0, 5, replica=i % 4)
            for i in range(40)]
    for s in sent:
        s.request.future = types.SimpleNamespace(
            done=lambda: True, exception=lambda: None)
    for seed in (1, 2, 2 ** 33 + 1):
        picked = check.sample(sent, seed, served_tokens=1)
        assert {check.replica(s) for s in picked} == {0, 1, 2, 3}
        assert len(picked) == len({id(s) for s in picked})
        assert picked[0] is sent[-1]            # the longest first
        solo = [s for s in sent if s.request.replica == 0]
        assert len(check.sample(solo, seed, served_tokens=1)) \
            == check.MIN_SAMPLE


FOUR_DEVICES = f"""
import json, sys
from pathlib import Path
sys.path.insert(0, {str(BENCH_DIR)!r})
import jax
from chipbench import faults, runner, serving, spec

layout = spec.Layout(configs=Path({str(DATA / "configs")!r}),
                     traffic=Path({str(DATA / "traffic")!r}))
cells = {{"tiny-dense.tiny_mix": ("tiny_mix", 1),
          "tiny-dense.tiny_burst.x4": ("tiny_burst", 4)}}
real = spec.load_benchmark()
bench = {{**real,
         "workloads": [{{"name": n, "config": "tiny-dense", "traffic": t,
                        "chips": c, "why": "test"}}
                       for n, (t, c) in cells.items()],
         "end_to_end": [{{k: v for k, v in m.items() if k != "workloads"}}
                        for m in real["end_to_end"]],
         "per_layer": [{{k: v for k, v in m.items() if k != "workloads"}}
                       for m in real["per_layer"]
                       if m["name"] != "route_imbalance"]
         + [{{"name": "route_imbalance", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "ReplicaSet router",
             "moves": "ttft_p90_s",
             "workloads": ["tiny-dense.tiny_burst.x4"]}}]}}
built = []
build = serving.build


def spy(*a, **kw):
    model, rs = build(*a, **kw)
    built.append([[d.id for d in rs.placements()[e.name]]
                  for e in rs.engines])
    return model, rs


serving.build = spy
out = {{"devices": len(jax.devices())}}
cell = spec.resolve(bench, "tiny-dense.tiny_mix", layout)
rec = runner.serve_window(
    cell, runner.Options(cell.name, 7, 1.0, False), jax.devices(),
    runner.clock(), runner.device_mod.CompileLog(), layout=layout)
released = True      # each client's next request is due at its last's end
for c in range(cell.traffic["clients"]):
    mine = [s for s in rec.sent if s.client == c]
    released &= all(b.due == a.request.done_t for a, b in zip(mine, mine[1:]))
out["one_chip"] = {{
    "placements": built[-1], "chips": rec.chips,
    "replicas": len(rec.replicas_close),
    "clients": sorted({{s.client for s in rec.sent}}), "released": released}}
del rec


def run(seed, trace):
    return runner.run(["--workload", "tiny-dense.tiny_burst.x4", "--seed",
                       str(seed), "--seconds", "1.5", "--trace", str(trace)],
                      layout=layout, bench=bench, need_chip=False,
                      persistent_cache=False, trace_dir={{trace_dir!r}})


out["four_chips"] = run(2 ** 32 + 9, 1)
out["four_chips_placements"] = built[-1]
faults.FAULTS["altered_token_on_replica1"](setattr)
out["fault_on_replica1"] = run(2 ** 32 + 11, 0)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices(tmp_path_factory):
    trace_dir = str(tmp_path_factory.mktemp("prof"))
    stdout = run_devices(FOUR_DEVICES.replace("{trace_dir!r}",
                                              repr(trace_dir)),
                         n_devices=4, timeout=900)
    return json.loads(stdout.strip().splitlines()[-1])


def test_build_makes_one_replica_per_device(four_devices):
    assert four_devices["devices"] == 4
    assert four_devices["four_chips_placements"] == [[0], [1], [2], [3]]


def test_one_chip_cell_serves_one_replica_from_its_clients(four_devices):
    """With four devices present, a one-chip cell still serves one
    replica on the first, under the closed loop of its clients."""
    one = four_devices["one_chip"]
    assert one["placements"] == [[0]]
    assert one["chips"] == one["replicas"] == 1
    assert one["clients"] == [0, 1, 2, 3]       # tiny_mix's 4 clients
    assert one["released"] is True


def test_four_replica_open_loop_is_correct_and_read(four_devices):
    line = four_devices["four_chips"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["checked_replicas"] == [4, 4]
    names = set(line["metrics"])
    assert {"route_imbalance", "batch_occupancy", "prefill_token_fill",
            "host_gap_share", "queue_wait_p90_s",
            "client_late_p99_ms"} <= names
    assert line["metrics"]["route_imbalance"]["value"] >= 0


def test_a_fault_on_one_replica_is_not_correct(four_devices):
    line = four_devices["fault_on_replica1"]
    assert line["correct"] is False
    gap, limit = line["checks"]["logit_gap"]
    assert gap > limit

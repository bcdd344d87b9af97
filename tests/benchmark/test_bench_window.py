"""Window arithmetic: counter differences, percentiles over all requests,
timing from the due time, and each metric reader on a hand-made run."""
import json
import math
import types
from concurrent.futures import Future

import numpy as np
import pytest

from bench_paths import BENCH_DIR

from chipbench import device, spec
from chipbench.load import Sent
from chipbench.window import RunRecord, percentile

COST = spec.load_module(BENCH_DIR / "cost", "dense_gqa")
MODEL = json.loads((BENCH_DIR / "configs" / "yi-9b-pp2.json").read_text())[
    "model"]


def read(name, run):
    return spec.load_module(BENCH_DIR / "metrics", name).read(run)


class Span:
    def __init__(self, name, t0, t1, children=(), events=(), **attrs):
        self.name, self.t0, self.t1 = name, t0, t1
        self.children, self.events, self.attrs = list(children), list(
            events), attrs


def request(prompt, due, first, done, n, error=None, spans=()):
    r = types.SimpleNamespace(first_token_t=first, done_t=done,
                              generated=[1] * n, future=Future(),
                              trace=types.SimpleNamespace(
                                  root=Span("request", due, done,
                                            children=spans)))
    if error is None:
        r.future.set_result(None)
    else:
        r.future.set_exception(error)
    return Sent(index=0, client=0, due=due, prompt=np.ones(prompt, np.int32),
                max_new=n, submit=due + 0.001, request=r)


def run_of(sent, **counters):
    return RunRecord(
        cell="c", seconds=10.0, t_open=100.0, t_close=110.0, setup_s=42.0,
        counters_open={k: 1000 for k in counters},
        counters_close={k: 1000 + v for k, v in counters.items()},
        sent=sent, model=MODEL, deployment={"slots": 4},
        cost=COST, peaks=device.peaks("TPU v5 lite"),
        device={"memory_peak_bytes": 15_000_000_000})


def test_percentile_linear_and_missing():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(range(101), 95) == 95
    assert percentile([3.0, 1.0, 2.0, 4.0], 90) == pytest.approx(
        float(np.percentile([1, 2, 3, 4], 90)))
    assert percentile([], 90) is None
    assert percentile([1.0] * 9 + [math.inf], 95) == math.inf


def test_counts_and_rates_are_window_differences():
    run = run_of([], tokens=500, decode_steps=200)
    assert run.count("tokens") == 500 and run.window_s == 10.0
    assert read("out_tok_s", run) == 50.0
    assert read("batch_occupancy", run) == pytest.approx(62.5)  # 500/800
    assert read("decode_step_ms", run) == pytest.approx(50.0)
    assert read("setup_s", run) == 42.0
    assert read("hbm_peak_gb", run) == 15.0


def test_tails_select_by_due_time_and_first_token():
    sent = [
        # due before the window: not in the ttft tail; first token inside
        request(100, 95.0, 101.0, 104.0, 31),
        # due inside, 0.5 s to first token, 10 ms per token after it
        request(100, 102.0, 102.5, 103.5, 101),
        # due inside, failed: missing, so infinitely late
        request(100, 103.0, None, None, 0, error=RuntimeError("x")),
        # first token after the window: no tpot
        request(100, 109.5, 110.5, 111.0, 11),
    ]
    run = run_of(sent, tokens=1)
    assert [s.due for s in run.due_in_window()] == [102.0, 103.0, 109.5]
    assert [s.due for s in run.first_token_in_window()] == [95.0, 102.0]
    # tpot: 3 s / 30 = 100 ms, and 1 s / 100 = 10 ms
    assert read("tpot_p95_ms", run) == pytest.approx(10 + 0.95 * 90)
    # the failed request's rank decides the 90th percentile: left out
    assert read("ttft_p90_s", run) is None
    assert read("client_late_p99_ms", run) == pytest.approx(1.0)
    ok = run_of(sent[:2] + sent[3:], tokens=1)
    assert read("ttft_p90_s", ok) == pytest.approx(0.5 + 0.9 * 0.5)


def test_token_contexts_spread_tokens_evenly():
    # 11 tokens, first at 105, last at 115: tokens 1..10 at 106..115;
    # those before 110 are tokens 1..4, at contexts 20+1 .. 20+4
    run = run_of([request(20, 104.0, 105.0, 115.0, 11)])
    n, ctx = run.token_contexts()
    assert n == 4 and ctx == 21 + 22 + 23 + 24


def test_mfu_and_bandwidth_from_the_cost_model():
    run = run_of([request(20, 104.0, 105.0, 115.0, 11)], decode_steps=5)
    flops = COST.prefill_flops(MODEL, 20) + 4 * COST.decode_flops(
        MODEL, 0) + COST.attention_flops(MODEL, 90)
    assert read("mfu", run) == pytest.approx(100 * flops / (197e12 * 10))
    need = 5 * COST.param_bytes(MODEL) + 90 * COST.kv_bytes_per_position(
        MODEL)
    assert read("decode_bw_share", run) == pytest.approx(
        100 * need / (819e9 * 10))
    assert read("mfu", run_of([])) is None      # never a share of 0


def test_prefill_row_fill_from_spans():
    qw = Span("queue_wait", 100.5, 101.0, replica="r0")
    # a padded group of 2 (of 4 slots) and a lone request: 3 real, 8 rows
    padded = [Span("prefill", 101.0, 101.1, mode="batched", group=2),
              Span("prefill", 101.0, 101.1, mode="batched", group=2),
              Span("prefill", 103.0, 103.1, mode="batched", group=1)]
    # chunk steps: two slots together (4 rows), then one alone (1 row)
    c1 = Span("prefill", 104.0, 106.0, mode="chunked",
              events=[(104.0, "chunk", {}), (105.0, "chunk", {})])
    c2 = Span("prefill", 104.0, 106.0, mode="chunked",
              events=[(104.0002, "chunk", {})])
    sent = [request(8, 100.5, 102, 103, 2, spans=[qw, s])
            for s in padded + [c1, c2]]
    run = run_of(sent)
    # real rows 3 + 3 chunks; computed 4 + 4 (padded), 4 + 1 (chunks)
    assert read("prefill_row_fill", run) == pytest.approx(100 * 6 / 13)
    assert read("queue_wait_p90_s", run) == pytest.approx(0.5)


def test_device_idle_share_from_the_trace_summary():
    run = run_of([])
    assert read("device_idle_share", run) is None
    run.profile = {"busy_s": 2.7, "window_s": 3.0}
    assert read("device_idle_share", run) == pytest.approx(10.0)

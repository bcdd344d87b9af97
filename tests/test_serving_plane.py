"""Async serving plane: batched prefill parity, replica failover, and
load-driven autoscaling."""
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.monitoring import Monitor
from repro.models.model import build_model
from repro.serving.autoscaler import Autoscaler, AutoscalerConfig
from repro.serving.engine import ServingEngine, greedy_generate
from repro.serving.replica import ReplicaSet


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("yi-9b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _factory(model, params, monitor=None, slots=2, max_seq=96):
    def make(i):
        return ServingEngine(model, params, slots=slots, max_seq=max_seq,
                             name=f"r{i}", monitor=monitor)
    return make


# -- batched prefill ---------------------------------------------------------

def test_batched_prefill_parity_with_oracle(served_model):
    """Mixed-length prompts admitted in one group, each prefilled alone at
    its own padded length, must decode exactly like the sequential
    oracle."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=4, max_seq=96)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n in (4, 11, 6, 15)]
    futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_idle()
    # all four admitted at once -> one prefill call per request
    assert eng.metrics["prefills"] == 4
    assert eng.metrics["prefill_requests"] == 4
    for p, f in zip(prompts, futs):
        ref = greedy_generate(model, params, p, 5, 96)
        np.testing.assert_array_equal(f.result(), ref)


def test_padding_safe_prefill_one_row_per_call(served_model):
    """Admission groups of mixed sizes and lengths: every prefill call
    carries one row, the prefill compiles once per length bucket whatever
    the group sizes, and the tokens match the oracle."""
    cfg, model, params = served_model
    max_seq = 112          # an engine key no other test compiles
    eng = ServingEngine(model, params, slots=3, max_seq=max_seq,
                        prefill_bucket=16)
    assert eng._pad_ok
    rows, groups = [], []
    prefill, prefill_group = eng._prefill, eng._prefill_group

    def spy_prefill(p, toks):
        rows.append(toks.shape)
        return prefill(p, toks)

    def spy_group(grp):
        groups.append(len(grp))
        return prefill_group(grp)
    eng._prefill, eng._prefill_group = spy_prefill, spy_group
    compiled = prefill._cache_size()
    rng = np.random.default_rng(5)
    lengths = (5, 20, 33, 12, 40, 7, 17, 31)
    news = (3, 7, 2, 5, 4, 6, 2, 3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in lengths]
    futs = [eng.submit(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    eng.run_until_idle()
    one = [eng.submit(prompts[3], max_new_tokens=2)]
    eng.run_until_idle()
    assert len(set(groups)) > 1 and sum(groups) == len(lengths) + 1
    assert rows and all(r[0] == 1 for r in rows)
    assert eng.metrics["prefills"] == len(rows) == len(lengths) + 1
    assert prefill._cache_size() - compiled == len({r[1] for r in rows}) \
        == len({eng._bucket_len(n) for n in lengths}) == 3
    for p, k, f in zip(prompts + prompts[3:4], news + (2,), futs + one):
        np.testing.assert_array_equal(
            f.result(), greedy_generate(model, params, p, k, max_seq))


def test_padding_safe_prefill_failure_fails_only_its_request(served_model):
    """A prefill call that raises fails its own request; the others of its
    admission group still go in and decode exactly."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=3, max_seq=96)
    prefill, calls = eng._prefill, []

    def flaky(p, toks):
        calls.append(toks.shape)
        if len(calls) == 2:
            raise RuntimeError("injected prefill fault")
        return prefill(p, toks)
    eng._prefill = flaky
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 14, 9)]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle()
    assert len(calls) == 3
    with pytest.raises(RuntimeError, match="injected"):
        futs[1].result(timeout=0)
    for i in (0, 2):
        np.testing.assert_array_equal(
            futs[i].result(timeout=0),
            greedy_generate(model, params, prompts[i], 4, 96))
    assert eng.metrics["prefill_requests"] == 2
    assert all(a is None for a in eng.active)


def test_rolling_cache_model_groups_by_length():
    """Sliding-window (rolling cache) models cannot take padded batches;
    the engine must fall back to per-length groups and stay exact."""
    cfg = reduced(get_config("gemma2-27b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServingEngine(model, params, slots=3, max_seq=96)
    assert not eng._pad_ok
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9, 5)]
    futs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_idle()
    # lengths {5, 9, 5} -> two groups (5s batched together), not three calls
    assert eng.metrics["prefills"] == 2
    for p, f in zip(prompts, futs):
        ref = greedy_generate(model, params, p, 4, 96)
        np.testing.assert_array_equal(f.result(), ref)


def test_moe_and_ssm_models_refuse_padding():
    """MoE capacity routing couples flattened batch tokens and SSM state
    absorbs pads — both must take the exact per-length path."""
    from repro.serving.engine import _padding_safe
    moe = build_model(reduced(get_config("granite-moe-1b-a400m")))
    ssm = build_model(reduced(get_config("mamba2-370m")))
    assert not _padding_safe(moe, 96)
    assert not _padding_safe(ssm, 96)


def test_oversize_prompt_rejected(served_model):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=2, max_seq=32)
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 40), max_new_tokens=4)   # 39 toks > 31
    with pytest.raises(ValueError):
        eng.submit(np.zeros((0,), np.int32))


def test_async_decode_loop_start_stop(served_model):
    """The background decode loop serves requests and honors the stop
    signal."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=2, max_seq=64)
    eng.start()
    assert eng.running
    f = eng.submit(np.arange(1, 6), max_new_tokens=4)
    out = f.result(timeout=120)
    assert len(out) == 4
    r = eng.submit_request(np.arange(1, 6), max_new_tokens=4)
    r.future.result(timeout=120)
    assert r.ttft_s is not None and r.latency_s is not None
    assert r.latency_s >= r.ttft_s
    eng.stop()
    assert not eng.running


# -- failover ----------------------------------------------------------------

def test_replica_failure_failover_completes_all(served_model):
    """Killing a replica mid-flight must not lose requests: the health sweep
    harvests them and healthy replicas finish every future with oracle-exact
    tokens."""
    cfg, model, params = served_model
    mon = Monitor()
    rs = ReplicaSet(_factory(model, params, mon), replicas=2, monitor=mon,
                    check_interval=0.02)
    rs.start()
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(n))
               for n in rng.integers(4, 12, size=8)]
    try:
        # warm the compile caches so the kill lands mid-decode, not mid-compile
        rs.submit_request(prompts[0], max_new_tokens=2).future.result(
            timeout=300)
        reqs = [rs.submit_request(p, max_new_tokens=6) for p in prompts]
        rs.engines[0].kill()
        outs = [r.future.result(timeout=300) for r in reqs]
    finally:
        rs.stop()
    assert len(outs) == len(prompts)
    for p, o in zip(prompts, outs):
        ref = greedy_generate(model, params, p, 6, 96)
        np.testing.assert_array_equal(o, ref)
    m = rs.metrics()
    assert m["failovers"] >= 1
    assert all(e.name != "r0" for e in rs.engines)     # dead replica removed


def test_failover_respawns_when_pool_empties(served_model):
    """A 1-replica set with respawn keeps serving after a crash (paper:
    reschedule the container)."""
    cfg, model, params = served_model
    rs = ReplicaSet(_factory(model, params), replicas=1,
                    check_interval=0.02, respawn=True)
    rs.start()
    try:
        rs.submit_request(np.arange(1, 5), max_new_tokens=2).future.result(
            timeout=300)
        r = rs.submit_request(np.arange(1, 7), max_new_tokens=4)
        rs.engines[0].kill()
        out = r.future.result(timeout=300)
    finally:
        rs.stop()
    assert len(out) == 4
    assert rs.size == 1 and rs.metrics()["failovers"] == 1


# -- autoscaler --------------------------------------------------------------

class _FakeEngine:
    """Load-bearing stub: the autoscaler only reads load/heartbeat/health."""
    n = 0

    def __init__(self, load=0):
        self.name = f"fake{_FakeEngine.n}"
        _FakeEngine.n += 1
        self._load = load
        self.heartbeat = time.monotonic()
        self.metrics = {}
        self.queue = None

    def start(self):
        return self

    def stop(self, timeout=None):
        return True

    def healthy(self):
        return True

    def harvest_requests(self):
        return []

    @property
    def load(self):
        return self._load

    @property
    def running(self):
        return True


def _fake_rs(loads):
    rs = ReplicaSet(lambda i: _FakeEngine(), replicas=len(loads))
    for e, ld in zip(rs.engines, loads):
        e._load = ld
    return rs


def test_autoscaler_scales_up_under_load():
    mon = Monitor()
    rs = _fake_rs([6, 6])
    a = Autoscaler(rs, mon, AutoscalerConfig(min_replicas=1, max_replicas=4,
                                             scale_up_load=3.0))
    assert a.evaluate() == "up"
    assert rs.size == 3
    assert a.evaluate() == "up"          # 12/3 = 4 > 3, still hot
    assert rs.size == 4
    assert a.evaluate() == "hold"        # at max, no resize hook
    assert any(k == ("lm-server", "autoscale.up")
               for k in mon._counters)


def test_autoscaler_scales_down_when_idle():
    mon = Monitor()
    rs = _fake_rs([0, 0, 0])
    a = Autoscaler(rs, mon, AutoscalerConfig(min_replicas=1, max_replicas=4,
                                             scale_down_load=0.5))
    assert a.evaluate() == "down"
    assert rs.size == 2
    assert a.evaluate() == "down"
    assert rs.size == 1
    assert a.evaluate() == "hold"        # at min


def test_autoscaler_triggers_mesh_resize_at_saturation():
    """At max replicas and still hot, the autoscaler pulls the second
    elasticity lever: the VRE mesh-resize hook."""
    mon = Monitor()
    rs = _fake_rs([9, 9])
    hits = []
    a = Autoscaler(rs, mon, AutoscalerConfig(min_replicas=1, max_replicas=2,
                                             scale_up_load=3.0),
                   resize_mesh=lambda: hits.append(1))
    assert a.evaluate() == "resize"
    assert hits == [1]


def test_vre_request_resize_records_pending(tmp_path):
    import repro.core.services  # noqa: F401
    from repro.core.vre import VREConfig, VirtualResearchEnvironment
    vre = VirtualResearchEnvironment(VREConfig(
        name="rz", mesh_shape=(1, 1), services=[], workdir=str(tmp_path)))
    vre.instantiate()
    assert vre.request_resize() == (2, 1)
    assert vre.pending_resize == (2, 1)
    vre.destroy()


# -- monitoring gauges -------------------------------------------------------

def test_monitor_rolling_gauges():
    mon = Monitor(gauge_window=8)
    for v in range(20):
        mon.gauge("svc", "queue_depth", v)
    s = mon.gauge_stats("svc", "queue_depth")
    assert s["n"] == 8                    # rolling window retains the tail
    assert s["last"] == 19.0
    assert s["p50"] == 16.0
    assert s["p95"] == 19.0
    assert mon.gauge_stats("svc", "missing")["n"] == 0
    assert "svc/queue_depth" in mon.summarize()["gauges"]

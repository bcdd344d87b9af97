import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.serving.engine import EdgeRouter, ServingEngine, greedy_generate


def _model():
    cfg = reduced(get_config("yi-9b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_matches_greedy_oracle():
    cfg, model, params = _model()
    eng = ServingEngine(model, params, slots=3, max_seq=96)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n in (5, 9, 13, 7)]
    futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_idle()
    for p, f in zip(prompts, futs):
        ref = greedy_generate(model, params, p, 6, 96)
        np.testing.assert_array_equal(f.result(), ref)


def test_continuous_batching_slot_reuse():
    cfg, model, params = _model()
    eng = ServingEngine(model, params, slots=2, max_seq=64)
    futs = [eng.submit(np.arange(1, 5), max_new_tokens=4) for _ in range(5)]
    eng.run_until_idle()
    outs = [f.result() for f in futs]
    assert all(len(o) == 4 for o in outs)
    for o in outs[1:]:                      # identical prompts -> identical
        np.testing.assert_array_equal(o, outs[0])
    # every request prefilled, each in a call of its own
    assert eng.metrics["prefill_requests"] == 5
    assert eng.metrics["prefills"] == 5


def test_edge_router_balances():
    cfg, model, params = _model()
    engines = [ServingEngine(model, params, slots=2, max_seq=64,
                             name=f"r{i}") for i in range(2)]
    router = EdgeRouter(engines)
    for _ in range(6):
        router.submit(np.arange(1, 6), max_new_tokens=3)
    router.drain()
    m = router.metrics()
    assert m["r0"]["requests"] + m["r1"]["requests"] == 6
    assert abs(m["r0"]["requests"] - m["r1"]["requests"]) <= 2

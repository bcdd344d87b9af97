"""The engine's own tracing: named programs, loop phases on the profiler's
clock, and the counters of prefill positions and host gaps."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileOptions

from repro.configs import get_config, reduced
from repro.models.model import build_model
from repro.serving.engine import ServingEngine
from repro.serving.prefix_cache import PrefixCache
from repro.serving.speculative import NgramDraft

MAX_SEQ = 96
CHUNK = 16
PHASES = ("serve.admit", "serve.prefill", "serve.chunk", "serve.decode",
          "serve.fetch", "serve.emit", "serve.wait")


@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(get_config("yi-9b"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _prompts(cfg, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, size=n) for n in lengths]


# -- prefill positions --------------------------------------------------------

# (chunk_tokens, prompt lengths, positions computed): a group of two, each
# prompt alone in its own bucket (16 and 32 positions); one prompt in three
# batch-1 chunks; two prompts chunked together twice (3 rows x 16 each),
# then the longer alone
PREFILL_CASES = {
    "padded_group": (None, (5, 20), 16 + 32),
    "batch1_chunk": (CHUNK, (40,), 3 * CHUNK),
    "batched_chunk": (CHUNK, (40, 20), 2 * 3 * CHUNK + CHUNK),
}


@pytest.mark.parametrize("case", list(PREFILL_CASES))
def test_prefill_positions_counted_where_computed(served_model, case):
    cfg, model, params = served_model
    chunk, lengths, computed = PREFILL_CASES[case]
    eng = ServingEngine(model, params, slots=3, max_seq=MAX_SEQ,
                        prefill_bucket=16, chunk_tokens=chunk)
    for p in _prompts(cfg, lengths):
        eng.submit(p, max_new_tokens=2)
    eng.run_until_idle()
    m = eng.metrics
    assert m["prefill_positions_computed"] == computed
    assert m["prefill_positions_real"] == sum(lengths)
    if case == "padded_group":
        assert (m["prefills"], m["prefill_chunks"]) == (2, 0)
    elif case == "batch1_chunk":
        assert (m["prefill_chunks"], m["prefill_chunk_batches"]) == (3, 0)
    else:
        assert (m["prefill_chunks"], m["prefill_chunk_batches"]) == (5, 2)


# -- host gap -----------------------------------------------------------------

def test_host_gap_within_wall_time_sync(served_model):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=3, max_seq=MAX_SEQ)
    for p in _prompts(cfg, (5, 9, 12)):
        eng.submit(p, max_new_tokens=6)
    t0 = time.perf_counter()
    eng.run_until_idle()
    wall = time.perf_counter() - t0
    assert eng.metrics["decode_steps"] >= 5
    assert 0.0 < eng.metrics["host_gap_s"] <= wall


def test_host_gap_excludes_waits(served_model):
    """An idle stretch of the async loop is spent in ``_wake.wait``, which
    the gap leaves out: the gap stays well below the idle time."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=2, max_seq=MAX_SEQ)
    a, b = _prompts(cfg, (6, 7))
    eng.submit(a, max_new_tokens=3)     # compile outside the timed part
    eng.run_until_idle()
    before = eng.metrics["host_gap_s"]
    idle = 0.5
    t0 = time.perf_counter()
    eng.start()
    try:
        eng.submit(a, max_new_tokens=3).result(timeout=60)
        time.sleep(idle)
        eng.submit(b, max_new_tokens=3).result(timeout=60)
    finally:
        assert eng.stop()
    wall = time.perf_counter() - t0
    gap = eng.metrics["host_gap_s"] - before
    assert 0.0 <= gap <= wall
    assert gap < 0.5 * idle


# -- program names ------------------------------------------------------------

@pytest.fixture(scope="module")
def lowered(served_model):
    """Each program of an engine with chunking, a prefix cache and
    speculation, lowered, by its name."""
    _cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=2, max_seq=MAX_SEQ,
                        chunk_tokens=CHUNK,
                        prefix_cache=PrefixCache(CHUNK, budget_bytes=1 << 20),
                        speculate=2, draft=NgramDraft())
    assert eng._chunk_ok and eng._spec_ok
    c, slots, i32 = eng.cache, eng.slots, np.int32
    zeros = lambda *shape: jnp.zeros(shape, jnp.int32)   # noqa: E731
    entry = eng._pc_extract(c, i32(0), i32(0), CHUNK)
    return {
        "serve_decode": eng._decode.lower(params, c, zeros(slots, 1),
                                          zeros(slots)),
        "serve_prefill": eng._prefill.lower(params, zeros(slots, 16)),
        "serve_chunk": eng._chunk.lower(params, c, zeros(1, CHUNK),
                                        zeros(1), i32(0)),
        "serve_chunk_batch": eng._chunk_batched.lower(
            params, c, zeros(slots, CHUNK), zeros(slots), zeros(slots)),
        "serve_verify": eng._verify.lower(params, c, zeros(slots, 3),
                                          zeros(slots)),
        "serve_prefix_restore": eng._pc_restore.lower(c, entry, i32(0)),
        "serve_prefix_extract": eng._pc_extract.lower(c, i32(0), i32(0),
                                                      CHUNK),
    }


@pytest.mark.parametrize("name", [
    "serve_decode", "serve_prefill", "serve_chunk", "serve_chunk_batch",
    "serve_verify", "serve_prefix_restore", "serve_prefix_extract"])
def test_lowered_module_names(lowered, name):
    first = lowered[name].as_text().splitlines()[0]
    assert f"module @jit_{name} " in first, first


# -- phases in a profiler trace -----------------------------------------------

def _trace_events(directory):
    path = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                     recursive=True)
    assert path, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(path[0])
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append(((plane.name, k), e.name, float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns)))
    return out


def test_phases_in_profiler_trace_are_leaves(served_model, tmp_path):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, slots=2, max_seq=MAX_SEQ,
                        chunk_tokens=CHUNK)
    warm = _prompts(cfg, (5, 30), seed=1)
    for p in warm:
        eng.submit(p, max_new_tokens=3)
    eng.run_until_idle()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for p in _prompts(cfg, (5, 30), seed=2):
            eng.submit(p, max_new_tokens=4)
        eng.run_until_idle()
        eng.start()
        try:
            eng.submit(warm[0], max_new_tokens=3).result(timeout=60)
            time.sleep(0.05)
        finally:
            assert eng.stop()
    finally:
        jax.profiler.stop_trace()
    events = _trace_events(str(tmp_path))
    names = {name for _, name, _, _ in events}
    assert names == set(PHASES)
    by_thread = {}
    for thread, name, a, b in events:
        by_thread.setdefault(thread, []).append((a, b, name))
    for spans in by_thread.values():
        spans.sort()
        for (a0, b0, n0), (a1, b1, n1) in zip(spans, spans[1:]):
            assert b0 <= a1, f"{n0} [{a0}, {b0}) overlaps {n1} [{a1}, {b1})"

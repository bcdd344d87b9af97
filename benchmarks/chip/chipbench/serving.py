"""The system under test: the program's normal serving path.

``build`` makes it as ``repro.launch.serve.build_replicaset`` does, from a
configuration file: ``build_model`` -> ``ServingEngine`` factory ->
``ReplicaSet``, with the weights the benchmark made from the seed
(``reference/<family>.program_params``) in place of the program's own
initializer. ``warm`` drives each engine through every shape the mix can
produce before the load starts.
"""
from __future__ import annotations

import numpy as np


def model_config(config: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(**config["model"])


def check_layout(model, params) -> None:
    """The benchmark's weights must have exactly the program's parameter
    layout: the same tree, shapes and types."""
    import jax
    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise RuntimeError(
            "the program's parameter layout differs from the benchmark's "
            f"weights: program {want}, benchmark {got}")


class SpanSink:
    """Attached as the engines' flight recorder in a traced run, so that
    each request carries its span tree; keeps nothing itself (the harness
    reads the spans from the requests it sent)."""

    def record(self, request, engine) -> None:
        pass

    def stop(self) -> None:
        pass


def build(config: dict, params, devices, *, traced: bool):
    """(model, ReplicaSet) for the configuration's deployment: one
    replica on the first device."""
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine
    from repro.serving.replica import ReplicaSet

    dep = config["deployment"]
    model = build_model(model_config(config))
    check_layout(model, params)
    sink = SpanSink() if traced else None

    def factory(i: int, devs=None):
        return ServingEngine(
            model, params, slots=dep["slots"], max_seq=dep["max_seq"],
            name=f"replica{i}", devices=devs,
            prefill_bucket=dep.get("prefill_bucket", 16),
            chunk_tokens=dep.get("chunk_tokens") or None, recorder=sink)

    rs = ReplicaSet(factory, replicas=1, devices=list(devices)[:1],
                    recorder=sink)
    return model, rs


def warm_plan(bounds: tuple, dep: dict) -> list:
    """Groups of prompt lengths that, admitted one group at a time, make
    every program the mix can reach: each padded-prefill bucket in the
    prompt range, each admission group size (its scatter is a shape of its
    own), the chunk program alone and batched, and decode."""
    lo, hi = bounds
    slots, b = int(dep["slots"]), int(dep.get("prefill_bucket", 16))
    chunk = int(dep.get("chunk_tokens") or 0)
    rounds = []
    top = min(hi, chunk) if chunk else hi
    if lo <= top:
        first = -(-lo // b) * b
        buckets = list(range(first, -(-top // b) * b + 1, b))
        for r in range(max(len(buckets), slots)):
            n = min(buckets[r % len(buckets)], top)
            rounds.append([n] * (r % slots + 1))
    if chunk and hi > chunk:
        rounds += [[hi], [hi, hi]]
    return rounds


def warm(engines, bounds: tuple, dep: dict, vocab: int) -> int:
    """Run ``warm_plan`` synchronously on every engine (before the pool's
    decode loops start); returns the requests it served."""
    rng = np.random.default_rng(0)
    served = 0
    for eng in engines:
        for group in warm_plan(bounds, dep):
            reqs = [eng.submit_request(rng.integers(1, vocab, size=n),
                                       max_new_tokens=1) for n in group]
            eng.run_until_idle()
            for r in reqs:
                r.future.result(timeout=0)
            served += len(reqs)
    return served

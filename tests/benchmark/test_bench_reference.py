"""The benchmark's weights and its plain float32 reference, against the
program's model at ``reduced()`` size, in float32 on the CPU."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH_DIR, DATA

from chipbench import serving, spec

REF = spec.load_module(BENCH_DIR / "reference", "dense_gqa")
TINY = json.loads((DATA / "configs" / "tiny-dense.json").read_text())
SEED = 2 ** 33 + 5


def test_served_weights_are_the_references_bit_for_bit():
    m = REF.dims(TINY["model"])
    params = REF.program_params(TINY["model"], SEED)
    key = REF.seed_key(SEED)
    for i in range(m.L):
        one = REF.layer_weights(m, key, i)
        got = jax.tree.map(lambda x: x[i], params["blocks"][0])
        assert jax.tree.structure(one) == jax.tree.structure(got)
        for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(got)):
            assert a.dtype == jnp.bfloat16
            assert np.array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))
    other = REF.program_params(TINY["model"], SEED + 1)
    assert not np.array_equal(np.asarray(params["final_norm"], np.float32),
                              np.asarray(other["final_norm"], np.float32))


def test_weights_have_the_programs_layout():
    params = REF.program_params(TINY["model"], SEED)
    from repro.models.model import build_model
    model = build_model(serving.model_config(TINY))
    serving.check_layout(model, params)
    with pytest.raises(RuntimeError):
        serving.check_layout(model, {"embed": params["embed"]})


def _reduced_model(arch):
    from repro.configs import get_config, reduced
    cfg = dataclasses.replace(reduced(get_config(arch)), dtype="float32")
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
              if not isinstance(getattr(cfg, f.name), (tuple, type(None)))
              and not dataclasses.is_dataclass(getattr(cfg, f.name))}
    return cfg, fields


def _program(cfg, seed, model_dict):
    from repro.models.model import build_model
    model = build_model(cfg)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          REF.program_params(model_dict, seed))
    return model, params


@pytest.mark.parametrize("arch", ["yi-9b", "qwen2-72b"])
def test_reference_matches_the_program_in_float32(arch):
    cfg, md = _reduced_model(arch)
    model, params = _program(cfg, SEED, md)
    toks = np.random.default_rng(0).integers(1, cfg.vocab_size, 40)
    got = np.asarray(model.forward(params, jnp.asarray(toks)[None])[0][0])
    want = REF.logits_at(md, SEED, [toks], [np.arange(40)])["f32"][0]
    scale = np.abs(want).max()
    assert np.abs(got[:, :cfg.vocab_size] - want).max() < 1e-4 * scale


@pytest.mark.parametrize("arch", ["yi-9b", "qwen2-72b"])
def test_cache_path_matches_and_a_rotated_decode_fails(arch):
    """Prefill then decode through the cache agrees with the reference;
    the same decode one position off does not."""
    cfg, md = _reduced_model(arch)
    model, params = _program(cfg, SEED, md)
    toks = np.random.default_rng(1).integers(1, cfg.vocab_size, 24)
    want = REF.logits_at(md, SEED, [toks], [np.arange(15, 24)])["f32"]
    scale = np.abs(want[0]).max()

    def served(shift):
        logits, cache = model.prefill(params, jnp.asarray(toks[None, :16]),
                                      64)
        out = [np.asarray(logits[0, -1])]
        for p in range(16, 24):
            logits, cache = model.decode(
                params, cache, jnp.asarray([[toks[p]]]),
                jnp.asarray([p + shift], jnp.int32))
            out.append(np.asarray(logits[0, 0]))
        return np.stack(out)[:, :cfg.vocab_size]

    assert np.abs(served(0) - want[0]).max() < 1e-4 * scale
    assert np.abs(served(1) - want[0]).max() > 1e-2 * scale


def test_fp8_control_departs_from_float32():
    toks = np.random.default_rng(2).integers(1, 503, 48)
    out = REF.logits_at(TINY["model"], SEED, [toks], [np.arange(48)],
                        modes=("f32", "fp8"))
    f32, q = out["f32"][0], out["fp8"][0]
    rel = np.abs(f32 - q).max() / np.abs(f32).max()
    assert 1e-3 < rel < 0.5

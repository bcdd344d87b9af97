"""The cost model's operations and bytes against hand-computed numbers."""
import json

import pytest

from bench_paths import BENCH_DIR

from chipbench import spec

cost = spec.load_module(BENCH_DIR / "cost", "dense_gqa")


def model(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())[
        "model"]


def test_yi_9b_pp2_by_hand():
    m = model("yi-9b-pp2")
    # q 4096*32*128 + k,v 2*4096*4*128 + o 32*128*4096 + mlp 3*4096*11008
    per_layer = 16777216 + 4194304 + 16777216 + 135266304
    assert cost.layer_weights(m) == per_layer == 173015040
    assert cost.head_flops(m) == 2 * 4096 * 64000
    assert cost.attention_flops(m, 1000) == 4 * 24 * 32 * 128 * 1000
    assert cost.decode_flops(m, 300) == (2 * 24 * per_layer
                                         + 4 * 24 * 32 * 128 * 300
                                         + 2 * 4096 * 64000)
    # a 2-token prompt attends 1 + 2 positions; one head at its end
    assert cost.prefill_flops(m, 2) == (2 * 2 * 24 * per_layer
                                        + 4 * 24 * 32 * 128 * 3
                                        + 2 * 4096 * 64000)
    # 24 layers of weights and two norms, the final norm, the embedding
    assert cost.param_bytes(m) == 2 * (24 * (per_layer + 2 * 4096) + 4096
                                       + 64000 * 4096)
    assert cost.param_bytes(m) == pytest.approx(8.83e9, rel=2e-3)
    assert cost.kv_bytes_per_position(m) == 2 * 2 * 24 * 4 * 128 == 49152


def test_qwen2_72b_pp16_by_hand():
    m = model("qwen2-72b-pp16")
    per_layer = (8192 * 64 * 128 * 2 + 2 * 8192 * 8 * 128
                 + 3 * 8192 * 29568)
    assert cost.layer_weights(m) == per_layer == 877658112
    biases = (64 + 2 * 8) * 128
    assert cost.param_bytes(m) == 2 * (5 * (per_layer + 2 * 8192 + biases)
                                       + 8192 + 152064 * 8192)
    assert cost.param_bytes(m) == pytest.approx(11.27e9, rel=2e-3)
    assert cost.kv_bytes_per_position(m) == 20480
    assert cost.head_flops(m) == 2 * 8192 * 152064
    assert cost.token_flops(m, 10) == 2 * 5 * per_layer + 4 * 5 * 64 * 128 * 10


def test_bytes_match_the_programs_parameter_count():
    from repro.configs.base import ModelConfig
    for name in ("yi-9b-pp2", "qwen2-72b-pp16"):
        m = model(name)
        # the vocabularies are multiples of the program's padding, so the
        # program holds exactly the weights the cost model counts
        assert cost.param_bytes(m) == 2 * ModelConfig(**m).param_count()

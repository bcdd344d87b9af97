"""Yi 9B — llama-architecture GQA.  [arXiv:2403.04652; hf:01-ai/Yi-9B]"""
import dataclasses

from repro.configs.base import ModelConfig, ServedCut

CONFIG = ModelConfig(
    name="yi-9b",
    family="dense",
    num_layers=48,
    d_model=4096,
    num_heads=32,
    num_kv_heads=4,
    head_dim=128,
    d_ff=11008,
    vocab_size=64000,
    rope_theta=10_000.0,
    skip_shapes=("long_500k",),
    source="arXiv:2403.04652; hf",
)

# All 48 layers are 8.57 B parameters, 17.13 GB in bf16: more than one v5e's
# 16 GiB before any KV cache. The deployment this stands for is two chips,
# each holding one 24-layer pipeline stage; one chip serves one stage with
# every width as published (4.41 B parameters, 8.83 GB).
SERVED = ServedCut(
    config=dataclasses.replace(CONFIG, num_layers=24),
    source="hf:01-ai/Yi-9B config.json (arXiv:2403.04652): hidden 4096, "
           "32 q / 4 kv heads x 128, intermediate 11008, vocab 64000, "
           "48 layers",
    reduced={"num_layers": "48 -> 24: this chip's pipeline stage; the "
                           "other 24 layers would be the second stage"},
    assumed={
        "tie_embeddings": "True: this repo's dense family shares the input "
                          "embedding with the output head; the source's "
                          "setting was not checked here",
        "norm_eps": "1e-6: repo default, not checked against the source",
        "stage_ends": "the stage holds both the embedding and the output "
                      "head, which in the deployment sit on the first and "
                      "the last stage",
    },
    deployment="2 x TPU v5e, pipeline-parallel: stage k holds layers "
               "24k..24k+23 whole; no layer is split across chips",
    chips_per_layer=1,
)

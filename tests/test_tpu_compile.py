"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: the TPU compiler, installed beside the CPU backend, compiles
each kernel for one chip of a described (not attached) ``v5e:2x2`` host.
That catches what interpret mode cannot: blocks that break the (8, 128)
tiling rule, primitives Mosaic cannot lower, and VMEM overruns.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.grouped_matmul.kernel import grouped_matmul_kernel
from repro.kernels.ssd.kernel import ssd_intra_chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_attention_compiles_at_yi_9b_widths(one_chip):
    cfg = get_config("yi-9b")
    # one sequence's heads at a 2048-token prompt, kv already expanded
    shape = (cfg.num_heads, 2048, cfg.head_dim)
    _compile(lambda q, k, v: flash_attention_kernel(q, k, v), one_chip,
             *[(shape, jnp.bfloat16)] * 3)


def test_grouped_matmul_compiles(one_chip):
    _compile(lambda x, w: grouped_matmul_kernel(x, w), one_chip,
             ((8, 256, 1024), jnp.bfloat16), ((8, 1024, 512), jnp.bfloat16))


def test_ssd_intra_chunk_compiles_at_mamba2_370m_widths(one_chip):
    cfg = get_config("mamba2-370m")
    s = cfg.ssm
    nh, hd, ds, c = s.num_heads(cfg.d_model), s.head_dim, s.d_state, \
        s.chunk_size
    b, nc = 1, 4
    _compile(lambda a, x, bm, cm: ssd_intra_chunk(a, x, bm, cm), one_chip,
             ((b, nh, nc, c), jnp.float32), ((b, nh, nc, c, hd), jnp.float32),
             ((b, nc, c, ds), jnp.float32), ((b, nc, c, ds), jnp.float32))

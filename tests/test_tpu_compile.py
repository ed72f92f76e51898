"""Compile the serving path's Pallas kernels for a TPU v5e chip, no chip needed.

The TPU compiler that ships with jax compiles for a topology that is
described rather than attached, so Mosaic's tiling and VMEM checks run here
at real widths.  Interpret mode checks none of them: these tests are what
catches a BlockSpec the chip would refuse.

The topology is described only inside a fixture.  Only one process may load
the TPU library, so describing it at import time would make every pytest
worker but one fail to collect this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import qwen2_1_5b
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.kv_append.kernel import kv_append_chunk
from repro.kernels.paged_attention.kernel import paged_attention_chunk
from repro.models import build_model
from repro.models.spec import abstract_params

CFG = qwen2_1_5b.CONFIG
H, KV, D = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
B, N_PAGES, POOL_PAGES = 8, 16, 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape_of(topo):
    """ShapeDtypeStruct factory placed on chip 0 of the described topology.

    The persistent compilation cache is off while these compiles run: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T,C", [(128, 1), (128, 128), (16, 1), (16, 16)])
def test_paged_attention_chunk_compiles(shape_of, T, C):
    assert (H, KV, D) == (12, 2, 128)
    compiled = paged_attention_chunk.lower(
        shape_of((B, C, H, D), jnp.bfloat16),
        shape_of((POOL_PAGES, T, KV, D), jnp.bfloat16),
        shape_of((POOL_PAGES, T, KV, D), jnp.bfloat16),
        shape_of((B, N_PAGES), jnp.int32),
        shape_of((B,), jnp.int32),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("C", [1, 128])
def test_kv_append_chunk_compiles(shape_of, C):
    compiled = kv_append_chunk.lower(
        shape_of((POOL_PAGES, 128, KV, D), jnp.bfloat16),
        shape_of((B, C, KV, D), jnp.bfloat16),
        shape_of((B, C), jnp.int32),
        shape_of((B, C), jnp.int32),
    ).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles_at_4k(shape_of):
    S = 4096
    compiled = flash_attention.lower(
        shape_of((1, S, H, D), jnp.bfloat16),
        shape_of((1, S, KV, D), jnp.bfloat16),
        shape_of((1, S, KV, D), jnp.bfloat16),
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("C", [128, 1])
def test_serve_step_fits_one_chip_at_full_width(shape_of, monkeypatch, C):
    """The whole qwen2-1.5b step at published widths, as the engine runs
    it (8 slots x 2048 tokens, 128-token pages), with the Pallas kernels
    in it and within one v5e chip's 16 GB."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "pallas")
    api = build_model(CFG)

    def place(tree):
        return jax.tree.map(lambda s: shape_of(s.shape, s.dtype), tree)

    params = place(abstract_params(api.init_specs()))
    caches = place(jax.eval_shape(lambda: api.init_caches(B, 2048, 128)))
    compiled = jax.jit(api.serve_step).lower(
        params, shape_of((B, C), jnp.int32), caches,
        shape_of((B,), jnp.int32)).compile()
    _assert_kernel(compiled)
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, used

"""Pallas TPU KV-append scatter (the non-temporal-store analogue).

Each grid step writes ONE token's K/V into its staging page at
``pool[page_ids[b, c], slot_ids[b, c]]``.  Page and slot ids arrive as
scalar prefetch, so the destination block is resolved in the BlockSpec
index map and the write is a direct VMEM->HBM DMA of exactly one (KV, D)
tile — no read-modify-write of the pool, no gather/scatter HLO.

The grid is (B, C): a chunked-prefill step scatters up to C tokens per
sequence, so a chunk that crosses a page boundary simply lands in two
pages across consecutive grid steps — relink's partial-block-copy case
needs no special path.  Valid tokens' (page, slot) targets are unique
(controller staging exclusivity); pad tokens are routed by the caller to
unpublished slots or the reserved null page, so overlapping writes can
only touch bytes nothing ever reads.

``input_output_aliases`` donates the pool, making the append in-place: the
data plane mutates the page exactly like U-Split's movnt into a staging
file, while the page table (metadata) is untouched until the page fills.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _append_kernel(pid_ref, sid_ref, new_ref, pool_in_ref, pool_ref):
    del pid_ref, sid_ref, pool_in_ref
    pool_ref[0, 0] = new_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def kv_append_chunk(
    pool: jnp.ndarray,        # [P, T, KV, D]
    new: jnp.ndarray,         # [B, C, KV, D]
    page_ids: jnp.ndarray,    # [B, C] int32
    slot_ids: jnp.ndarray,    # [B, C] int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    B, C, KV, D = new.shape
    P, T, KVp, Dp = pool.shape
    assert (KV, D) == (KVp, Dp)
    assert page_ids.shape == slot_ids.shape == (B, C)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, C),
        in_specs=[
            pl.BlockSpec((1, 1, KV, D), lambda b, c, pid, sid: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, KV, D),
                         lambda b, c, pid, sid: (pid[b, c], sid[b, c], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, KV, D),
                               lambda b, c, pid, sid: (pid[b, c], sid[b, c], 0, 0)),
    )
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        name="kv_append_chunk",
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(page_ids, slot_ids, new, pool)


def kv_append(
    pool: jnp.ndarray,        # [P, T, KV, D]
    new: jnp.ndarray,         # [B, KV, D]
    page_ids: jnp.ndarray,    # [B] int32
    slot_ids: jnp.ndarray,    # [B] int32
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-token append: the C=1 slice of the chunk scatter."""
    return kv_append_chunk(pool, new[:, None], page_ids[:, None],
                           slot_ids[:, None], interpret=interpret)

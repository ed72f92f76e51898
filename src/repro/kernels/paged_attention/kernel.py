"""Pallas TPU paged attention: chunked queries over the page pool.

A chunk of C query tokens per sequence (C=1 for decode) attends over KV
pages addressed by a page table.  The page table and sequence lengths ride
in as *scalar prefetch* operands, so each grid step's BlockSpec index map
dereferences ``page_table[b, n]`` — the pool page is DMA'd straight from
HBM into VMEM with no gather materialization.  This is the device-side
collection-of-mmaps: the kernel walks the extent map exactly like U-Split
routes a read.

Queries arrive flattened to rows [C * group, D] per kv head; row r belongs
to query token ``r // group`` at absolute position ``lengths[b] + r//group``
and causality is enforced PER ROW inside the chunk — prefill's in-chunk
triangle and decode's single row are the same mask expression.

Grid ``(B, n_pages)`` with pages innermost (sequential).  Each step DMAs
one whole page, all KV heads of it, and loops over the heads inside the
kernel; the online-softmax state of each head lives in VMEM scratch.
Pages past the chunk's last query position — and pages wholly outside the
sliding window for local-attention layers — are skipped via ``pl.when``
(the staging-page analogue: allocated but unpublished pages cost nothing).

VMEM per step, double-buffered: a K and a V page (T*KV*D each, KV padded to
the sublane tile), q and out (KV*C*group*D each), plus f32 state
(KV*C*group*(D + 2*128), m and l padded to a lane row).  For qwen2-1.5b
(KV=2, group=6, D=128) at T=C=128 that is about 6 MB.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, kpool_ref, vpool_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_tokens: int, group: int,
                  q_tokens: int, kv_heads: int, window: Optional[int],
                  softcap: Optional[float], num_page_steps: int):
    b = pl.program_id(0)
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = len_ref[b]                     # pre-chunk length = first q position
    page_lo = n * page_tokens
    run = page_lo < start + q_tokens       # last query sits at start+q_tokens-1
    if window is not None:
        # first query's window floor is start - window; skip pages wholly below
        run = jnp.logical_and(run, page_lo + page_tokens > start - window)

    @pl.when(run)
    def _compute():
        shape = (q_tokens * group, page_tokens)              # [CG, T]
        kpos = page_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        qpos = start + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // group
        mask = kpos <= qpos                                  # chunk-causal
        if window is not None:
            mask &= kpos > qpos - window
        for h in range(kv_heads):          # the page block holds every head
            q = q_ref[0, h].astype(jnp.float32)              # [CG, D]
            k = kpool_ref[0, :, h, :].astype(jnp.float32)    # [T, D]
            v = vpool_ref[0, :, h, :].astype(jnp.float32)    # [T, D]
            scale = q.shape[-1] ** -0.5
            s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, NEG_INF)

            m_prev = m_ref[h, :, 0]
            m_curr = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(m_prev - m_curr)
            p = jnp.where(mask, jnp.exp(s - m_curr[:, None]), 0.0)
            l_ref[h, :, 0] = l_ref[h, :, 0] * alpha + p.sum(axis=-1)
            m_ref[h, :, 0] = m_curr
            acc_ref[h] = acc_ref[h] * alpha[:, None] + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(n == num_page_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "interpret"),
)
def paged_attention_chunk(
    q: jnp.ndarray,            # [B, C, H, D]
    pool_k: jnp.ndarray,       # [P, T, KV, D]
    pool_v: jnp.ndarray,       # [P, T, KV, D]
    page_table: jnp.ndarray,   # [B, N] int32
    lengths: jnp.ndarray,      # [B] int32      (PRE-chunk length)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    B, C, H, D = q.shape
    P, T, KV, _ = pool_k.shape
    N = page_table.shape[1]
    group = H // KV
    assert H % KV == 0
    CG = C * group

    kernel = functools.partial(
        _paged_kernel, page_tokens=T, group=group, q_tokens=C, kv_heads=KV,
        window=window, softcap=softcap, num_page_steps=N)
    # The page block spans all KV heads: its last two dims (KV, D) equal the
    # pool's, which Mosaic accepts for any KV (a one-head block is refused
    # unless KV % 8 == 0).
    page_spec = pl.BlockSpec((1, T, KV, D),
                             lambda b, n, pt, ln: (pt[b, n], 0, 0, 0))
    q_spec = pl.BlockSpec((1, KV, CG, D), lambda b, n, pt, ln: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N),
        in_specs=[q_spec, page_spec, page_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((KV, CG, 1), jnp.float32),
            pltpu.VMEM((KV, CG, 1), jnp.float32),
            pltpu.VMEM((KV, CG, D), jnp.float32),
        ],
    )
    # rows flatten (token, head-in-group): row r -> token r // group
    qh = q.reshape(B, C, KV, group, D).transpose(0, 2, 1, 3, 4)  # [B,KV,C,G,D]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        name="paged_attention_chunk",
        out_shape=jax.ShapeDtypeStruct((B, KV, CG, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(page_table, lengths, qh.reshape(B, KV, CG, D), pool_k, pool_v)
    out = out.reshape(B, KV, C, group, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, C, H, D)


def paged_attention(
    q: jnp.ndarray,            # [B, H, D]
    pool_k: jnp.ndarray,       # [P, T, KV, D]
    pool_v: jnp.ndarray,       # [P, T, KV, D]
    page_table: jnp.ndarray,   # [B, N] int32
    lengths: jnp.ndarray,      # [B] int32      (TOTAL valid keys)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-query decode: the C=1 slice of the chunk kernel (the last
    valid key IS the query position, so pre-length = lengths - 1)."""
    out = paged_attention_chunk(q[:, None], pool_k, pool_v, page_table,
                                lengths - 1, window=window, softcap=softcap,
                                interpret=interpret)
    return out[:, 0]

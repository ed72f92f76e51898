"""Pallas TPU flash attention (causal / sliding-window / GQA).

Grid ``(B, H, nQ, nK)`` with the KV dimension innermost (sequential on TPU);
the online-softmax state (m, l, acc) lives in VMEM scratch and is carried
across KV steps of one (b, h, q-block).  Blocks that are entirely outside
the causal/window band are skipped with ``pl.when`` — for a 2 K window over
a 32 K sequence only ~2/32 of the KV blocks are touched, which is where the
sub-quadratic long-context cost comes from on the TPU target.

The wrapper moves heads ahead of sequence (``[B, H, S, D]``) so that every
block's last two dims are ``(block, D)``, the tiling Mosaic accepts for any
head count.  BlockSpec tiling: q/out ``(1, 1, BQ, D)``, k/v
``(1, 1, BK, D)`` with the KV head picked by ``h // group`` in the index
map (GQA without materializing repeated heads).  VMEM working set =
BQ*D + 2*BK*D + BQ*BK floats — with the default BQ=BK=512, D=128 that is
~1.6 MB, comfortably inside the ~16 MB VMEM budget and MXU-aligned
(multiples of 128 everywhere).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)                   # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)                   # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [BQ, BK]
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                   # [BQ]
        m_curr = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_curr)
        p = jnp.exp(s - m_curr[:, None])
        p = jnp.where(mask, p, 0.0)
        l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=-1)
        m_ref[:, 0] = m_curr
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[:, 0], 1e-20)[:, None]
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "interpret"),
)
def flash_attention(
    q: jnp.ndarray,            # [B, Sq, H, D]
    k: jnp.ndarray,            # [B, Sk, KV, D]
    v: jnp.ndarray,            # [B, Sk, KV, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Sk, KV, _ = k.shape
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    n_q = Sq // block_q
    n_k = Sk // block_k
    scale = D ** -0.5

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, block_q=block_q, block_k=block_k, num_k_blocks=n_k)

    q_spec = pl.BlockSpec((1, 1, block_q, D),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, h, qi, ki: (b, h // group, ki, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),   # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)

"""Operations and bytes the algorithm needs, from a configuration file's
sizes and the live lengths of each call.  Never from the program's padded
shapes, page tables or grids: the yardstick stays the same whatever
implements the step.

``Dims`` holds the sizes of a dense GQA model; the family module
``bench/families/dense_gqa.py`` reads them from a configuration file's
published keys.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Optional, Tuple

BF16_BYTES = 2


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool            # SwiGLU: gate, up and down; else up and down
    window: Optional[int]      # sliding window in keys, None for full


def keys_seen(d: Dims, pos: int) -> int:
    """Keys the query at position ``pos`` attends to (itself included)."""
    n = pos + 1
    return n if d.window is None else min(n, d.window)


def linear_flops_per_token(d: Dims) -> int:
    """2 x the multiply-adds of every weight matrix a token meets: the
    attention projections and the MLP of each layer, and the unembedding.
    Norms, biases, RoPE and the softmax are left out (under 0.1 %)."""
    attn = d.d_model * (2 * d.heads + 2 * d.kv_heads) * d.head_dim
    mlp = (3 if d.gated_mlp else 2) * d.d_model * d.d_ff
    return 2 * (d.layers * (attn + mlp) + d.d_model * d.vocab)


def attn_flops_per_token(d: Dims, pos: int) -> int:
    """QK^T and PV of one query over its keys, every head of one layer."""
    return 4 * d.heads * d.head_dim * keys_seen(d, pos)


def _attn_flops_chunk(d: Dims, n: int, start: int) -> int:
    """Sum of ``attn_flops_per_token`` over positions start .. start+n-1."""
    if d.window is None or start + n <= d.window:
        keys = n * (2 * start + n + 1) // 2          # sum of (p + 1)
    else:
        keys = sum(keys_seen(d, p) for p in range(start, start + n))
    return 4 * d.heads * d.head_dim * keys


def model_flops(d: Dims, fed: Iterable[Tuple[int, int]]) -> int:
    """Model FLOPs of one step's valid tokens: ``fed`` holds (tokens fed,
    cached length before them) per slot.  Pad positions and tokens served
    from the prefix cache are not fed, so they do not count."""
    total = 0
    lin = linear_flops_per_token(d)
    for n, start in fed:
        total += n * lin + d.layers * _attn_flops_chunk(d, n, start)
    return total


def paged_attention_cost(d: Dims, fed: Iterable[Tuple[int, int]]
                         ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one step's paged-attention calls, one call per
    layer.  A slot that feeds ``n`` tokens after ``start`` cached ones reads
    the K and V of the keys its queries see, [max(0, start + 1 - window),
    start + n), and reads q and writes out for its ``n`` queries."""
    flops = byts = 0
    for n, start in fed:
        flops += _attn_flops_chunk(d, n, start)
        first = 0 if d.window is None else max(0, start + 1 - d.window)
        kv_tokens = start + n - first
        byts += 2 * kv_tokens * d.kv_heads * d.head_dim * BF16_BYTES
        byts += 2 * n * d.heads * d.head_dim * BF16_BYTES
    return flops * d.layers, byts * d.layers


def kv_append_cost(d: Dims, fed: Iterable[Tuple[int, int]]
                   ) -> Tuple[int, int]:
    """(FLOPs, bytes) of one step's KV-append calls, one for K and one for
    V per layer: each reads the rows of the ``n`` tokens a slot feeds and
    writes them into the pool.  No arithmetic."""
    tokens = sum(n for n, _ in fed)
    return 0, d.layers * 2 * 2 * tokens * d.kv_heads * d.head_dim * BF16_BYTES


def roofline_seconds(flops: float, byts: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """The least time the chip needs, and which bound sets it."""
    tf, tb = flops / peak_flops, byts / peak_bw
    return (tf, "compute") if tf >= tb else (tb, "memory")

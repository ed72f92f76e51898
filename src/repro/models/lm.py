"""Decoder-only LM over heterogeneous block patterns with grouped scan.

Layers are grouped by the config's ``block_pattern`` period (1 for uniform
archs; e.g. ("rec","rec","attn") for recurrentgemma).  Full groups scan with
stacked parameters — one compiled group body regardless of depth — and the
non-periodic tail runs unrolled.  Decode threads paged-KV pools / recurrent
state through the same group structure.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .blocks import block_cache_init, block_init, block_serve, block_train
from .config import ModelConfig
from .layers import norm_apply, norm_init
from .shardctx import constrain_batch
from ..scan_util import maybe_scan
from .spec import ParamSpec, is_spec, tree_map_specs


# ---------------------------------------------------------------------------
# structure helpers
# ---------------------------------------------------------------------------


def _pattern_groups(cfg: ModelConfig) -> Tuple[Tuple[str, ...], int, Tuple[str, ...]]:
    """(period_pattern, n_full_groups, tail_pattern)."""
    pattern = cfg.block_pattern or ("attn",)
    period = len(pattern)
    n_full = cfg.n_layers // period
    tail = tuple(cfg.pattern_for_layers()[n_full * period:])
    return tuple(pattern), n_full, tail


def _stack_specs(tree: Any, n: int) -> Any:
    return tree_map_specs(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.logical, s.dtype,
                            s.init, s.scale), tree)


def n_attn_layers(cfg: ModelConfig) -> int:
    return sum(1 for k in cfg.pattern_for_layers() if k == "attn")


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def lm_init(cfg: ModelConfig) -> Dict:
    pattern, n_full, tail = _pattern_groups(cfg)
    group = {f"b{i}_{kind}": block_init(cfg, kind)
             for i, kind in enumerate(pattern)}
    params: Dict[str, Any] = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "embed_tbl"),
                           cfg.param_dtype, init="embed", scale=0.02),
        "group": _stack_specs(group, n_full),
        "final_norm": norm_init(cfg),
    }
    if tail:
        params["tail"] = {f"t{i}_{kind}": block_init(cfg, kind)
                          for i, kind in enumerate(tail)}
    if not cfg.tie_embeddings:
        params["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab),
                                      ("embed", "vocab"), cfg.param_dtype,
                                      scale=0.02)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------


def embed_tokens(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
                 prefix_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    x = params["embed"].astype(cfg.dtype)[tokens]
    if cfg.family == "hybrid":               # gemma-style embedding scale
        x = x * math.sqrt(cfg.d_model)
    if prefix_embeds is not None:            # VLM stub: patch embeddings
        x = jnp.concatenate([prefix_embeds.astype(cfg.dtype), x], axis=1)
    return constrain_batch(x)


def unembed(params: Dict, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return x @ params["embed"].astype(cfg.dtype).T
    return x @ params["lm_head"].astype(cfg.dtype)


def lm_hidden(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
              prefix_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    pattern, n_full, tail = _pattern_groups(cfg)
    x = embed_tokens(params, cfg, tokens, prefix_embeds)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def group_fn(carry, gp):
        h = carry
        for i, kind in enumerate(pattern):
            h = block_train(gp[f"b{i}_{kind}"], cfg, kind, h, positions)
        return h, None

    if cfg.remat == "full":
        group_fn = jax.checkpoint(group_fn,
                                  policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        group_fn = jax.checkpoint(
            group_fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    if n_full:
        x, _ = maybe_scan(group_fn, x, params["group"])
    for i, kind in enumerate(tail):
        x = block_train(params["tail"][f"t{i}_{kind}"], cfg, kind, x, positions)
    return norm_apply(params["final_norm"], cfg, x)


def lm_logits(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
              prefix_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    return unembed(params, cfg, lm_hidden(params, cfg, tokens, prefix_embeds))


def lm_loss(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
            targets: jnp.ndarray,
            prefix_embeds: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token cross entropy (float32 logits for stability)."""
    logits = constrain_batch(
        lm_logits(params, cfg, tokens, prefix_embeds)).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    # gather-free gold-logit extraction (masked reduce fuses; take_along_axis
    # is a vocab-dim gather that trips the SPMD partitioner in manual regions)
    cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(jnp.where(cols == targets[..., None], logits, 0.0), axis=-1)
    return (logz - gold).mean()


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------


def lm_init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                   page_tokens: int = 128,
                   pages_per_seq: Optional[int] = None) -> Dict:
    """Zeroed decode caches.  Pool sizing comes from
    ``cfg.kv_pages_per_seq`` — the same single-source formula the engine's
    ``api.kv_geometry`` uses, so controller metadata and device pools can
    never disagree.  (The engine's PagedKVCache may share pages; the
    compiled step only sees arrays + tables.)"""
    pattern, n_full, tail = _pattern_groups(cfg)
    if pages_per_seq is None:
        pages_per_seq = cfg.kv_pages_per_seq(max_seq, page_tokens)
    num_pages = max(batch * pages_per_seq, 1)

    def stack_caches(kind: str, n: int):
        one = block_cache_init(cfg, kind, batch, num_pages, page_tokens)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    caches: Dict[str, Any] = {
        "page_table": jnp.arange(batch * pages_per_seq, dtype=jnp.int32)
        .reshape(batch, pages_per_seq) % num_pages,
        "lengths": jnp.zeros((batch,), jnp.int32),
        "group": {f"b{i}_{kind}": stack_caches(kind, n_full)
                  for i, kind in enumerate(pattern)} if n_full else {},
        "tail": {f"t{i}_{kind}": block_cache_init(cfg, kind, batch, num_pages,
                                                  page_tokens)
                 for i, kind in enumerate(tail)},
    }
    return caches


@jax.named_scope("serve_step")
def lm_serve_step(params: Dict, cfg: ModelConfig, tokens: jnp.ndarray,
                  caches: Dict, n_new: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """Unified chunked serve step (prefill chunks AND decode in one
    fixed-shape program).  tokens: [B, C] with tokens[b, :n_new[b]] valid;
    positions run lengths[b] .. lengths[b]+C-1.  Returns
    (logits [B, C, V], new caches with lengths + n_new).  Decode is the
    degenerate C-slice: n_new == 1 and only logits[:, 0] meaningful."""
    pattern, n_full, tail = _pattern_groups(cfg)
    page_table = caches["page_table"]
    lengths = caches["lengths"]
    x = embed_tokens(params, cfg, tokens)

    # Caches ride in the scan CARRY (updated via dynamic_update_slice at
    # the layer index), NOT as xs/ys: while-loop carries alias in place, so
    # the pools exist once — xs/ys stacking double-buffers them (+21 GB/chip
    # at 72B/32K, see EXPERIMENTS.md §Perf).
    def group_fn(carry, xs):
        h, gcaches = carry
        layer_idx, gp = xs
        new_gc = {}
        for i, kind in enumerate(pattern):
            key = f"b{i}_{kind}"
            gc_i = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, layer_idx, 0,
                                                       keepdims=False),
                gcaches[key])
            h, out_i = block_serve(gp[key], cfg, kind, h, gc_i,
                                   page_table, lengths, n_new)
            new_gc[key] = jax.tree.map(
                lambda full, upd: jax.lax.dynamic_update_index_in_dim(
                    full, upd, layer_idx, 0),
                gcaches[key], out_i)
        return (h, new_gc), None

    new_caches: Dict[str, Any] = {"page_table": page_table,
                                  "lengths": lengths + n_new}
    if n_full:
        (x, new_group), _ = maybe_scan(
            group_fn, (x, caches["group"]),
            (jnp.arange(n_full), params["group"]))
        new_caches["group"] = new_group
    else:
        new_caches["group"] = {}
    new_caches["tail"] = {}
    for i, kind in enumerate(tail):
        key = f"t{i}_{kind}"
        x, new_caches["tail"][key] = block_serve(
            params["tail"][key], cfg, kind, x, caches["tail"][key],
            page_table, lengths, n_new)
    x = norm_apply(params["final_norm"], cfg, x)
    return unembed(params, cfg, x), new_caches

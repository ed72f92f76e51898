"""Plain reference of a dense decoder with grouped-query attention, in
float32 ``jax.numpy``: Qwen2 (RMSNorm, SwiGLU, q/k/v biases, tied
embedding) and StarCoder2 (LayerNorm, GELU-tanh MLP with biases, q/k/v
biases, sliding window) as their published configurations describe them.

It imports nothing of the system under test.  It reads the configuration
file's published keys and the weights the benchmark made, in the tree
layout the served model takes: ``embed [V, D]``, ``final_norm``, an
optional ``lm_head [D, V]``, and ``group/b0_attn`` with every layer's
``norm1``, ``attn`` (wq wk wv wo, bq bk bv), ``norm2`` and ``mlp``
(wi_gate wi_up wo, or wi bi wo bo) stacked on a leading layer axis.

StarCoder2 publishes a bias on the attention output projection too.  The
served model has no such leaf, so the weights the benchmark makes hold
none and this forward adds none: a departure of the program, the one
between it and the published architecture (PERF.md, Open questions).

Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32 product
otherwise runs in bfloat16 passes.  ``fp8=True`` gives the control: each
matmul's operands, and q, k and v, are rounded to float8 e4m3 with a scale
per row (per output column for weights) before the float32 product.

Attention runs over blocks of queries, and the layers in a scan, so that
the forward fits beside the served model's weights.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8_MAX = 448.0


def _q8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jnp.ndarray, w: jnp.ndarray, fp8: bool) -> jnp.ndarray:
    w = w.astype(jnp.float32)
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _norm(p: Dict, x: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    w = p["w"].astype(jnp.float32)
    if kind == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w \
            + p["b"].astype(jnp.float32)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate the halves of each head (GPT-NeoX convention).  x: [S, H, hd]."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) * 2.0 / hd)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None]     # [S, half]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class Arch:
    """The sizes and choices the forward needs, from a configuration file."""

    def __init__(self, cfg: Dict) -> None:
        self.d = int(cfg["hidden_size"])
        self.h = int(cfg["num_attention_heads"])
        self.kv = int(cfg["num_key_value_heads"])
        self.hd = int(cfg.get("head_dim") or self.d // self.h)
        self.theta = float(cfg["rope_theta"])
        self.window = cfg.get("sliding_window")
        if "rms_norm_eps" in cfg:
            self.norm, self.eps = "rmsnorm", float(cfg["rms_norm_eps"])
        else:
            self.norm, self.eps = "layernorm", float(cfg["norm_epsilon"])
        self.act = cfg["hidden_act"]
        self.tied = bool(cfg["tie_word_embeddings"])

    def key(self) -> Tuple:
        return (self.d, self.h, self.kv, self.hd, self.theta, self.window,
                self.norm, self.eps, self.act, self.tied)

    def __hash__(self) -> int:
        return hash(self.key())

    def __eq__(self, other) -> bool:
        return isinstance(other, Arch) and self.key() == other.key()


def _attention(a: Arch, q, k, v, fp8: bool):
    """Causal (and windowed) attention over blocks of queries.
    q: [S, H, hd]; k, v: [S, KV, hd]."""
    S = q.shape[0]
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    group = a.h // a.kv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    kpos = jnp.arange(S)[None, :]
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        qpos = jnp.arange(lo, lo + qb.shape[0])[:, None]
        mask = kpos <= qpos
        if a.window is not None:
            mask &= kpos > qpos - int(a.window)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(a.hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    return jnp.concatenate(outs, 0)


def _layer(a: Arch, fp8: bool, x, lp):
    S = x.shape[0]
    at = lp["attn"]
    h = _norm(lp["norm1"], x, a.norm, a.eps)
    q, k, v = (_mm(h, at[w], fp8) for w in ("wq", "wk", "wv"))
    if "bq" in at:
        q = q + at["bq"].astype(jnp.float32)
        k = k + at["bk"].astype(jnp.float32)
        v = v + at["bv"].astype(jnp.float32)
    q = _rope(q.reshape(S, a.h, a.hd), a.theta)
    k = _rope(k.reshape(S, a.kv, a.hd), a.theta)
    v = v.reshape(S, a.kv, a.hd)
    o = _attention(a, q, k, v, fp8).reshape(S, a.h * a.hd)
    x = x + _mm(o, at["wo"], fp8)
    h = _norm(lp["norm2"], x, a.norm, a.eps)
    m = lp["mlp"]
    if a.act == "silu":
        u = jax.nn.silu(_mm(h, m["wi_gate"], fp8)) * _mm(h, m["wi_up"], fp8)
        return x + _mm(u, m["wo"], fp8)
    u = _mm(h, m["wi"], fp8) + m["bi"].astype(jnp.float32)
    u = jax.nn.gelu(u, approximate=True)
    return x + _mm(u, m["wo"], fp8) + m["bo"].astype(jnp.float32)


def _hidden(a: Arch, fp8: bool, params, tokens):
    x = params["embed"][tokens].astype(jnp.float32)
    layers = params["group"]["b0_attn"]
    x, _ = jax.lax.scan(lambda c, lp: (_layer(a, fp8, c, lp), None),
                        x, layers)
    return _norm(params["final_norm"], x, a.norm, a.eps)


def _unembed(a: Arch, params, h, fp8: bool):
    w = params["embed"].T if a.tied else params["lm_head"]
    return _mm(h, w, fp8)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _forward(a: Arch, fp8: bool, params, tokens):
    """Final hidden state of every position: [S, D]."""
    return _hidden(a, fp8, params, tokens)


@functools.partial(jax.jit, static_argnums=(0,))
def _score(a: Arch, params, h, targets):
    """Float32 logits of ``h`` [K_BLOCK, D]: their maximum, and the logit
    of each row of ``targets`` [T, K_BLOCK]."""
    logits = _unembed(a, params, h, False)                       # [K, V]
    at = jnp.take_along_axis(logits[None], targets[..., None], -1)[..., 0]
    return logits.max(-1), at


@functools.partial(jax.jit, static_argnums=(0,))
def _control_top(a: Arch, params, h):
    return _unembed(a, params, h, True).argmax(-1).astype(jnp.int32)


K_BLOCK = 128


def _bucket(n: int) -> int:
    """Sequence lengths padded to a power of two of at least 1024, so a
    run compiles the forward for a few lengths only."""
    return max(1024, 1 << (max(n, 1) - 1).bit_length())


class Reference:
    """Scores a served request's output tokens against the float32 forward."""

    def __init__(self, cfg: Dict, params) -> None:
        self.arch = Arch(cfg)
        self.params = params

    def gaps(self, prompt, output, control: bool = False
             ) -> Tuple[np.ndarray, np.ndarray]:
        """Per output token: how far the served token's float32 logit lies
        below the float32 best at its position; and, with ``control``, the
        same for the token the float8 forward puts first (else empty)."""
        a = self.arch
        seq = list(prompt) + list(output[:-1])
        tokens = np.zeros(_bucket(len(seq)), np.int32)
        tokens[:len(seq)] = seq
        pos = np.arange(len(prompt) - 1, len(seq))     # predicts output[i]
        h = _forward(a, False, self.params, tokens)
        h8 = _forward(a, True, self.params, tokens) if control else None
        gap, ctrl = [], []
        for lo in range(0, len(pos), K_BLOCK):
            idx = np.zeros(K_BLOCK, np.int32)
            n = min(K_BLOCK, len(pos) - lo)
            idx[:n] = pos[lo:lo + n]
            served = np.zeros(K_BLOCK, np.int32)
            served[:n] = output[lo:lo + n]
            rows = [served]
            if control:
                rows.append(np.asarray(_control_top(a, self.params,
                                                    h8[idx])))
            best, at = _score(a, self.params, h[idx], np.stack(rows))
            g = np.asarray(best)[None, :n] - np.asarray(at)[:, :n]
            gap.append(g[0])
            if control:
                ctrl.append(g[1])
        return (np.concatenate(gap),
                np.concatenate(ctrl) if control else np.zeros(0, np.float32))

"""Dense decoders with grouped-query attention (Qwen2, StarCoder2): what
the harness needs to know of the family, from a configuration file's
published keys.

  READS            the published keys read here, each with what it sets
  LEAVES           published keys left to other code, each with why
  program_fields   the program's ``ModelConfig`` fields the file states
  model_flops      model FLOPs of one step's valid tokens
  KERNEL_COSTS     {op base name: (cfg, fed) -> (FLOPs, bytes)}: the
                   kernels the traced run sums apart, and their counts

``fed`` holds (tokens fed, cached length before them) per slot of one
step.  The arithmetic is ``bench/flops.py``'s.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from bench import flops

READS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim; hidden_size / heads where absent",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta",
    "sliding_window": "attn_window; absent or null is full attention",
    "rms_norm_eps": "present: RMSNorm, else LayerNorm (the program's eps "
                    "is 1e-6; the reference reads the value)",
    "hidden_act": "silu: SwiGLU, else the GELU MLP with biases",
    "attention_bias": "qkv_bias (Qwen2's name)",
    "use_bias": "qkv_bias (StarCoder2's name)",
}
LEAVES = {
    "architectures": "the family is chosen by the file's reference key",
    "torch_dtype": "read through serving.param_dtype",
    "norm_epsilon": "LayerNorm eps: the program's is 1e-5; the reference "
                    "reads the value",
    "max_position_embeddings": "the engine's context is serving.max_seq",
}


def _head_dim(cfg: Dict) -> int:
    return int(cfg.get("head_dim") or int(cfg["hidden_size"])
               // int(cfg["num_attention_heads"]))


def program_fields(cfg: Dict) -> Dict:
    """``ModelConfig`` overrides from the file's published keys."""
    return dict(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=_head_dim(cfg),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        attn_window=cfg.get("sliding_window"),
        norm="rmsnorm" if "rms_norm_eps" in cfg else "layernorm",
        mlp="swiglu" if cfg["hidden_act"] == "silu" else "gelu",
        qkv_bias=bool(cfg.get("attention_bias", cfg.get("use_bias"))))


def dims(cfg: Dict) -> flops.Dims:
    """The sizes ``bench/flops.py`` counts with."""
    return flops.Dims(
        layers=int(cfg["num_hidden_layers"]), d_model=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]), head_dim=_head_dim(cfg),
        d_ff=int(cfg["intermediate_size"]), vocab=int(cfg["vocab_size"]),
        gated_mlp=cfg["hidden_act"] == "silu",
        window=cfg.get("sliding_window"))


def model_flops(cfg: Dict, fed: Iterable[Tuple[int, int]]) -> int:
    return flops.model_flops(dims(cfg), fed)


KERNEL_COSTS = {
    "paged_attention_chunk":
        lambda cfg, fed: flops.paged_attention_cost(dims(cfg), fed),
    "kv_append_chunk":
        lambda cfg, fed: flops.kv_append_cost(dims(cfg), fed),
}

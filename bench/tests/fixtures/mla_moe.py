"""A family module the harness has never seen, for the test that a
configuration joins the benchmark with new files alone
(``bench/tests/test_bench_families.py`` copies it into a scratch
checkout's ``bench/families/``).  Latent attention and routed experts,
keyed as DeepSeek-V2's config.json keys them.

Its counts are the test's own, simple enough to check by hand: the
routed and shared MLPs and the head for ``model_flops``, the latent's
bytes for one kernel.  They are not the arithmetic of MLA or MoE.
"""

from typing import Dict, Iterable, Tuple

READS = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads and n_kv_heads",
    "intermediate_size": "d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "n_routed_experts": "n_experts",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim; head_dim is nope + rope",
    "v_head_dim": "v_head_dim",
    "vocab_size": "vocab",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}
LEAVES = {
    "torch_dtype": "read through serving.param_dtype",
    "rms_norm_eps": "RMSNorm at the program's fixed eps",
}


def program_fields(cfg: Dict) -> Dict:
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    heads = int(cfg["num_attention_heads"])
    return dict(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=heads, n_kv_heads=heads,
        head_dim=nope + rope, d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        n_experts=int(cfg["n_routed_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared_experts=int(cfg["n_shared_experts"]),
        moe_d_ff=int(cfg["moe_intermediate_size"]), mla=True,
        kv_lora_rank=int(cfg["kv_lora_rank"]), qk_nope_dim=nope,
        qk_rope_dim=rope, v_head_dim=int(cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]))


def flops_per_token(cfg: Dict) -> int:
    d, ff = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    experts = int(cfg["num_experts_per_tok"]) + int(cfg["n_shared_experts"])
    return 2 * (int(cfg["num_hidden_layers"]) * 3 * d * ff * experts
                + d * int(cfg["vocab_size"]))


def model_flops(cfg: Dict, fed: Iterable[Tuple[int, int]]) -> int:
    return flops_per_token(cfg) * sum(n for n, _ in fed)


def latent_cost(cfg: Dict, fed: Iterable[Tuple[int, int]]
                ) -> Tuple[int, int]:
    """No FLOPs; the bf16 latent of every key each slot's queries see,
    read once per layer."""
    width = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    keys = sum(start + n for n, start in fed)
    return 0, int(cfg["num_hidden_layers"]) * keys * width * 2


KERNEL_COSTS = {"mla_attention_chunk": latent_cost}

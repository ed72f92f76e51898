"""The code cell reads what it read before configurations found their
family module by name: the same served ModelConfig, the same FLOP and
byte counts, the same traced kernels and the same weights.  Every
expected value here was read from the harness as it stood before the
family modules."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, weights

CONFIG = harness.ROOT / "bench" / "configs" / "starcoder2-7b.pp2.json"
FED = [(128, 0), (64, 1500), (1, 4095), (128, 4000)]

SERVED = dict(
    name="starcoder2-7b", family="dense", n_layers=16, d_model=4608,
    n_heads=36, n_kv_heads=4, d_ff=18432, vocab=49152, head_dim=128,
    qkv_bias=True, rope_theta=1000000.0, attn_window=4096,
    attn_logit_softcap=None, norm="layernorm", mlp="gelu",
    tie_embeddings=False, n_experts=0, n_shared_experts=0, top_k=0,
    moe_d_ff=0, mla=False, kv_lora_rank=0, qk_rope_dim=0, qk_nope_dim=0,
    v_head_dim=0, ssm_state=0, ssm_conv=4, ssm_expand=2, ssm_head_dim=64,
    ssm_chunk=256, block_pattern=(), lru_width=None, n_enc_layers=0,
    n_dec_layers=0, enc_frames=1500, n_patch_tokens=0, dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16, remat="full")


def _code_config():
    cfg = harness.load_json(CONFIG)
    return cfg, harness.load_family(cfg)


def test_served_model_config_field_by_field():
    cfg, family = _code_config()
    pc = harness.program_config(cfg, family)
    for field, want in SERVED.items():
        assert getattr(pc, field) == want, field


def test_model_flops_and_attention_cost_at_the_files_widths():
    cfg, family = _code_config()
    assert family.model_flops(cfg, FED) == 2_560_841_220_096
    assert family.KERNEL_COSTS["paged_attention_chunk"](cfg, FED) == \
        (185_841_745_920, 419_594_240)


def test_traced_kernels():
    _, family = _code_config()
    assert list(family.KERNEL_COSTS) == ["paged_attention_chunk",
                                         "kv_append_chunk"]


def _small_code_tree():
    """The served tree of ``smoke.small_cell('starcoder2-7b.code_complete')``:
    d_model 64, 4/2 heads of 16, d_ff 128, vocab 2048, 2 layers."""
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    L = 2
    return {
        "embed": bf16(2048, 64),
        "final_norm": {"b": bf16(64), "w": bf16(64)},
        "group": {"b0_attn": {
            "attn": {"bk": bf16(L, 32), "bq": bf16(L, 64),
                     "bv": bf16(L, 32), "wk": bf16(L, 64, 32),
                     "wo": bf16(L, 64, 64), "wq": bf16(L, 64, 64),
                     "wv": bf16(L, 64, 32)},
            "mlp": {"bi": bf16(L, 128), "bo": bf16(L, 64),
                    "wi": bf16(L, 64, 128), "wo": bf16(L, 128, 64)},
            "norm1": {"b": bf16(L, 64), "w": bf16(L, 64)},
            "norm2": {"b": bf16(L, 64), "w": bf16(L, 64)}}},
        "lm_head": bf16(64, 2048)}


def test_weights_of_the_small_code_cell():
    from repro.models import build_model
    from repro.models.spec import abstract_params

    from bench.tests.smoke import small_cell

    cell = small_cell("starcoder2-7b.code_complete")
    pc = harness.program_config(cell.config, cell.family)
    assert abstract_params(build_model(pc).init_specs()) == \
        _small_code_tree()
    w = weights.make(_small_code_tree(), 2**31 + 7, 64, False)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update("/".join(weights._path_names(path)).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == \
        "cfc533a5c7c0bf3d2c366ca6e1543a2bf618171c78e6062c14cf34424b030f07"

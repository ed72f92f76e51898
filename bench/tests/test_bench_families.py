"""A configuration of a family the harness has never seen joins the
benchmark with new files alone: in a scratch checkout, a config file in
the program's ``deepseek-smoke`` shapes (latent attention and routed
experts, keyed as DeepSeek-V2's config.json keys them), its cell in
BENCHMARK.json, and a family module (``fixtures/mla_moe.py``).  No file
of the harness changes."""

import json
import shutil
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, layer_stats, weights

HERE = Path(__file__).resolve().parent
CELL = "deepseek-smoke.code_complete"
# src/repro/configs/deepseek_v2_lite_16b.py SMOKE, under published names
CONFIG = {
    "name": "deepseek-smoke",
    "source": "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "intermediate_size": 64, "moe_intermediate_size": 64,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "torch_dtype": "bfloat16",
    "reduced": {"num_hidden_layers": {"published": 27, "here": 2}},
    "serving": {"repo_config": "deepseek-v2-lite-16b",
                "param_dtype": "bfloat16", "max_batch": 4, "max_seq": 256,
                "page_tokens": 16, "prefix_cache": True},
    "reference": "mla_moe",
}


def checkout(root: Path, **extra) -> Path:
    """A checkout holding the repository's bench/ plus the new files."""
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests",
                                                  "testdata"))
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "deepseek-smoke", "source": CONFIG["source"],
        "file": "bench/configs/deepseek-smoke.json",
        "reduced": ["num_hidden_layers"], "why": "latent attention, experts"})
    bench["workloads"].append({
        "name": CELL, "config": "deepseek-smoke",
        "traffic": "code_complete", "chips": 1, "why": "the test's cell"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "configs" / "deepseek-smoke.json").write_text(
        json.dumps(dict(CONFIG, **extra)))
    shutil.copy(root / "bench" / "limits"
                / "starcoder2-7b.code_complete.json",
                root / "bench" / "limits" / f"{CELL}.json")
    shutil.copy(HERE / "fixtures" / "mla_moe.py",
                root / "bench" / "families" / "mla_moe.py")
    return root


def test_the_cell_loads_and_serves_the_files_sizes(tmp_path):
    cell = harness.load_cell(CELL, checkout(tmp_path))
    assert cell.config["reference"] == "mla_moe"
    assert list(cell.family.KERNEL_COSTS) == ["mla_attention_chunk"]
    pc = harness.program_config(cell.config, cell.family)
    # the program's own config holds 64 experts, top-6, 2 shared, rank 512
    assert (pc.n_experts, pc.top_k, pc.n_shared_experts, pc.moe_d_ff) == \
        (8, 2, 1, 64)
    assert pc.mla and pc.kv_lora_rank == 32
    assert (pc.qk_nope_dim, pc.qk_rope_dim, pc.v_head_dim, pc.head_dim) == \
        (16, 8, 16, 24)
    assert (pc.n_layers, pc.d_model, pc.vocab) == (2, 64, 512)
    assert pc.param_dtype == jnp.bfloat16
    # the code cell still loads beside it, with the dense family
    assert list(harness.load_cell("starcoder2-7b.code_complete",
                                  tmp_path).family.KERNEL_COSTS) == \
        ["paged_attention_chunk", "kv_append_chunk"]


@pytest.mark.parametrize("key", ["routed_scaling_factor",
                                 "first_k_dense_replace"])
def test_a_key_no_code_accounts_for_stops_the_harness(tmp_path, key):
    root = checkout(tmp_path, **{key: 1})
    with pytest.raises(SystemExit, match=key):
        harness.load_cell(CELL, root)


def test_a_latent_norm_is_drawn_as_a_gain():
    names = ("group", "b0_attn", "attn", "kv_norm", "w")
    key = jax.random.PRNGKey(0)
    gain = np.asarray(weights._leaf(key, names, (2, 4096), jnp.float32,
                                    64, False))
    assert abs(gain.mean() - 1.0) < 0.01 and abs(gain.std() - 0.1) < 0.01
    bias = np.asarray(weights._leaf(key, names[:3] + ("bkv",), (2, 4096),
                                    jnp.float32, 64, False))
    assert abs(bias.mean()) < 0.01


def test_the_readers_take_the_familys_counts(tmp_path):
    cell = harness.load_cell(CELL, checkout(tmp_path))
    fed = [(16, 0), (1, 40), (9, 100)]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    run = SimpleNamespace(
        window=(0.0, 2.0),
        steps=[SimpleNamespace(t0=0.5, t1=0.6, prefill=True, fed=fed)] * 3,
        trace=SimpleNamespace(window_s=2.0, devices=1,
                              kernel_ns={"mla_attention_chunk": 4e3,
                                         "paged_attention_chunk": 1e3}),
        cell={"family": cell.family, "config": cell.config, "peaks": peaks})
    # 2 x (2 layers x 3 matrices x 64 x 64 x 3 experts + 64 x 512)
    per_token = 2 * (2 * 3 * 64 * 64 * 3 + 64 * 512)
    assert per_token == 212_992
    assert layer_stats.mfu(run) == pytest.approx(
        100 * 3 * 26 * per_token / (2.0 * 197e12))
    # the latent, 32 + 8 wide, of 16 + 41 + 109 keys, over 2 layers
    latent_bytes = 3 * 2 * (16 + 41 + 109) * 40 * 2
    assert layer_stats.roofline(run, "mla_attention_chunk") == \
        pytest.approx(100 * (latent_bytes / 819e9) / 4e-6)
    # a kernel the family counts nothing for reads nothing
    assert layer_stats.roofline(run, "paged_attention_chunk") is None

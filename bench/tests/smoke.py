"""Cells cut to a size the CPU runs in seconds, for the rehearsal tests.

``small_cell`` takes a cell of BENCHMARK.json and keeps its files, with
small widths, few slots, short prompts and a short lead-in.  Only the
tests shrink a cell.

``rehearsal_cell`` builds a cell that BENCHMARK.json does not list, from
the mixes below, so the harness's paths that no listed cell takes yet
are driven end to end too: shared prefixes adopted from the prefix
cache, STRICT sessions and their operation-log check, and a saturated
backlog with ``out_tok_s``.  The mixes are the generator's own test
data, in the mix file format of ``bench/traffic/``.
"""

from bench import harness

SMALL = {
    "qwen2-1.5b": dict(hidden_size=64, intermediate_size=128,
                       num_attention_heads=4, num_key_value_heads=2,
                       num_hidden_layers=2, vocab_size=2048),
    "starcoder2-7b": dict(hidden_size=64, intermediate_size=128,
                          num_attention_heads=4, num_key_value_heads=2,
                          num_hidden_layers=2, vocab_size=2048,
                          sliding_window=48),
}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

# a Qwen2-shaped dense GQA model (RMSNorm, SwiGLU, q/k/v biases, tied
# embedding), at the widths of SMALL
QWEN2 = dict(SMALL["qwen2-1.5b"], name="qwen2-small",
             max_position_embeddings=4096, rms_norm_eps=1e-06,
             rope_theta=1000000.0, hidden_act="silu",
             tie_word_embeddings=True, attention_bias=True,
             serving={"repo_config": "qwen2-1.5b",
                      "param_dtype": "bfloat16", "max_batch": 8,
                      "max_seq": 4096, "page_tokens": 128,
                      "prefix_cache": True},
             reference="dense_gqa")

MIXES = {
    # chat sessions behind shared system prompts, a quarter STRICT
    "shared_prefix_strict": {
        "arrival": {"kind": "poisson", "rate_per_s": 0.12},
        "lead_in_s": 10, "drain_s": 60, "block": 16,
        "shared_prefix": {"count": 16, "tokens": 1024, "zipf_s": 1.0},
        "turns": {"min": 1, "max": 4, "gap_mean_s": 4.0},
        "prompt": {"median": 64, "sigma": 1.0, "min": 16, "max": 512},
        "output": {"median": 129, "sigma": 0.8, "min": 16, "max": 512},
        "strict_share": 0.25},
    # a saturated offline batch: two requests waiting per slot
    "backlog": {
        "arrival": {"kind": "backlog", "waiting_per_slot": 2},
        "lead_in_s": 15, "drain_s": 0, "block": 16,
        "prompt": {"median": 256, "sigma": 0.8, "min": 32, "max": 1024},
        "output": {"median": 384, "sigma": 0.6, "min": 64, "max": 1024},
        "strict_share": 0.0},
}
REHEARSAL_E2E = {
    "shared_prefix_strict": [{"name": "ttft_p75_ms", "unit": "ms"},
                             {"name": "itl_p90_ms", "unit": "ms"},
                             {"name": "setup_s", "unit": "s"}],
    "backlog": [{"name": "out_tok_s", "unit": "tokens/s"},
                {"name": "setup_s", "unit": "s"}],
}


def shrink(cell: harness.Cell) -> harness.Cell:
    cell.config = dict(cell.config,
                       **SMALL[cell.config["serving"]["repo_config"]])
    cell.config["serving"] = dict(cell.config["serving"], max_batch=4,
                                  max_seq=256, page_tokens=16)
    mix = dict(cell.traffic, lead_in_s=0.5, drain_s=30)
    mix["prompt"] = dict(mix["prompt"], median=24, min=4, max=40)
    mix["output"] = dict(mix["output"], median=8, min=2, max=16)
    if "shared_prefix" in mix:
        mix["shared_prefix"] = dict(mix["shared_prefix"], tokens=32, count=4)
    if mix["arrival"]["kind"] == "poisson":
        mix["arrival"] = dict(mix["arrival"], rate_per_s=4.0)
    cell.traffic = mix
    return cell


def small_cell(name: str) -> harness.Cell:
    return shrink(harness.load_cell(name))


def rehearsal_cell(mix: str) -> harness.Cell:
    root = harness.ROOT
    return shrink(harness.Cell(
        name=f"qwen2-small.{mix}", entry={"name": mix, "chips": 1},
        config=dict(QWEN2), traffic=MIXES[mix],
        limits={"max_logit_gap": 0.4}, end_to_end=REHEARSAL_E2E[mix],
        per_layer=[],
        peaks=harness.load_json(root / "bench" / "peaks.json"),
        family=harness.load_family(QWEN2)))

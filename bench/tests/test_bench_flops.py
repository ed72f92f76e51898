"""FLOP and byte functions against counts made by hand."""

import json
from pathlib import Path

import pytest

from bench import flops
from bench.families import dense_gqa

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
# Qwen2-1.5B's published sizes (arXiv:2407.10671): a gated MLP, full
# attention, a tied head
QWEN2_1_5B = {"hidden_size": 1536, "intermediate_size": 8960,
              "num_attention_heads": 12, "num_key_value_heads": 2,
              "num_hidden_layers": 28, "vocab_size": 151936,
              "hidden_act": "silu"}


def dims(name):
    if name == "qwen2-1.5b":
        return dense_gqa.dims(QWEN2_1_5B)
    return dense_gqa.dims(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen2_linear_flops_by_hand():
    d = dims("qwen2-1.5b")
    # per layer: q 1536x1536, k and v 1536x256, o 1536x1536, MLP 3 x 1536x8960
    layer = 1536 * 1536 * 2 + 1536 * 256 * 2 + 3 * 1536 * 8960
    head = 1536 * 151936
    assert flops.linear_flops_per_token(d) == 2 * (28 * layer + head)
    assert d.head_dim == 128 and d.gated_mlp and d.window is None


def test_starcoder2_linear_flops_by_hand():
    d = dims("starcoder2-7b.pp2")
    layer = 4608 * 4608 * 2 + 4608 * 512 * 2 + 2 * 4608 * 18432
    assert flops.linear_flops_per_token(d) == 2 * (16 * layer
                                                   + 4608 * 49152)
    assert d.window == 4096 and not d.gated_mlp


def test_attention_flops_of_a_chunk():
    d = dims("qwen2-1.5b")
    # 3 tokens after 5 cached: they see 6, 7 and 8 keys
    per_key = 4 * 12 * 128
    assert flops.model_flops(d, [(3, 5)]) == \
        3 * flops.linear_flops_per_token(d) + 28 * per_key * (6 + 7 + 8)
    f, b = flops.paged_attention_cost(d, [(3, 5)])
    assert f == 28 * per_key * 21
    # K and V of 8 tokens (2 heads x 128), q and out of 3 tokens (12 x 128)
    assert b == 28 * (2 * 8 * 2 * 128 * 2 + 2 * 3 * 12 * 128 * 2)


def test_window_bounds_the_keys():
    d = dims("starcoder2-7b.pp2")
    assert flops.keys_seen(d, 5000) == 4096
    assert flops.keys_seen(d, 10) == 11
    f, b = flops.paged_attention_cost(d, [(2, 4200)])
    assert f == 16 * 4 * 36 * 128 * (4096 + 4096)
    # keys from 4200 + 1 - 4096 = 105 to 4201
    assert b == 16 * (2 * (4202 - 105) * 4 * 128 * 2 + 2 * 2 * 36 * 128 * 2)


def test_kv_append_reads_and_writes_the_fed_rows():
    d = dims("starcoder2-7b.pp2")
    # 128 + 1 tokens, K and V, read once and written once, 4 heads x 128
    assert flops.kv_append_cost(d, [(128, 0), (1, 5000)]) == \
        (0, 16 * 2 * 2 * 129 * 4 * 128 * 2)
    assert flops.kv_append_cost(d, []) == (0, 0)


def test_decode_batch_sums_slots():
    d = dims("qwen2-1.5b")
    one = flops.model_flops(d, [(1, 100)])
    assert flops.model_flops(d, [(1, 100), (1, 100)]) == 2 * one
    assert flops.model_flops(d, []) == 0


@pytest.mark.parametrize("f,b,bound", [(197e12, 1.0, "compute"),
                                       (1.0, 819e9, "memory")])
def test_roofline_picks_the_larger_bound(f, b, bound):
    t, which = flops.roofline_seconds(f, b, 197e12, 819e9)
    assert which == bound and t == pytest.approx(1.0)

"""The trace reduction, on a small trace recorded on a v5e chip
(bench/testdata/small_window.xplane.pb: a traced 1-s window of a chat
cell's harness with qwen2-1.5b widths cut to 2 layers and 4 slots, 32
engine steps: one C=128 step and 31 decode steps; copied out of the
run's trace directory before the harness deleted it), and on made-up
intervals."""

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import flops, layer_stats, tracing
from bench.families import dense_gqa

# the trace's model: Qwen2-1.5B's widths, 2 layers
QWEN2_2_LAYERS = {"hidden_size": 1536, "intermediate_size": 8960,
                  "num_attention_heads": 12, "num_key_value_heads": 2,
                  "num_hidden_layers": 2, "vocab_size": 151936,
                  "hidden_act": "silu"}
TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "small_window.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return tracing.summarize(str(TRACE), ["paged_attention_chunk",
                                          "kv_append_chunk"])


def test_window_is_the_host_span(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(1.020983571, abs=1e-9)


def test_busy_is_the_union_of_ops(summary):
    assert summary.busy_s == pytest.approx(0.032639658, rel=1e-6)
    idle = sum(t for t, _ in summary.gaps)
    assert (idle + summary.busy_ns) == pytest.approx(
        summary.window_ns[1] - summary.window_ns[0], rel=1e-9)


def test_kernel_time_and_calls(summary):
    # one paged-attention call per layer per step; kv_append for K and V
    assert summary.kernel_calls == {"paged_attention_chunk": 2 * 32,
                                    "kv_append_chunk": 2 * 2 * 32}
    assert summary.kernel_ns["paged_attention_chunk"] == pytest.approx(
        462301.0)


def test_gaps_by_host_span(summary):
    b = summary.breakdown()
    name, secs = b["idle_gaps"][0]
    assert name == "bench.sleep" and secs == pytest.approx(0.652837878)
    # the one C=128 step: its logits go to the host while the device idles
    assert b["idle_gaps"][1][0] == "bench.step"
    assert b["idle_gaps"][1][1] > 0.2
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    # device ops carry their result shape, ordered by self time
    assert b["device_ops"][0][0].startswith(
        "convolution_bitcast_fusion = bf16[4,1,151936]")
    times = [t for _, t in b["device_ops"]]
    assert times == sorted(times, reverse=True)


def test_idle_share_and_roofline_readers(summary):
    run = SimpleNamespace(
        trace=summary, window=(0.0, 1.0),
        steps=[SimpleNamespace(t0=0.1, t1=0.2, prefill=False,
                               fed=[(1, 300)] * 4)],
        cell={"family": dense_gqa, "config": QWEN2_2_LAYERS,
              "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}})
    assert layer_stats.idle_share(run) == pytest.approx(
        100 * (1 - 0.032639658 / 1.020983571))
    f, b = flops.paged_attention_cost(
        flops.Dims(2, 1536, 12, 2, 128, 8960, 151936, True, None),
        [(1, 300)] * 4)
    least = max(f / 197e12, b / 819e9)
    assert layer_stats.roofline(run, "paged_attention_chunk") == \
        pytest.approx(100 * least / 462301e-9)
    assert layer_stats.roofline(run, "no_such_kernel") is None


def test_union_and_idle_intervals():
    spans = [(0, 2), (1, 3), (5, 6), (5.5, 5.6)]
    assert tracing.union_ns(spans) == 4
    assert tracing.idle_intervals(spans, -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    assert tracing.idle_intervals([], 0, 1) == [(0, 1)]


def test_self_time_subtracts_nested_ops():
    evs = [(0, 10, "while"), (1, 3, "a"), (4, 6, "b"), (12, 13, "c")]
    assert tracing._self_times(evs) == {"while": 6, "a": 2, "b": 2, "c": 1}


def test_op_names():
    name = "%paged_attention_chunk.6 = bf16[4,2,768,128]{3,2,1,0} custom-call(s32[4,8])"
    assert tracing.op_name(name) == "paged_attention_chunk.6"
    assert tracing.base_name("paged_attention_chunk.6") == \
        "paged_attention_chunk"
    assert tracing.base_name("copy") == "copy"
    assert tracing.op_label(name) == "paged_attention_chunk.6 = bf16[4,2,768,128]"


def test_host_span_at():
    host = [(0, 1, "bench.step"), (1.5, 2, "bench.sleep")]
    starts = [s for s, _, _ in host]
    assert tracing._host_span_at(host, starts, 0.5) == "bench.step"
    assert tracing._host_span_at(host, starts, 1.2) == "none"
    assert tracing._host_span_at(host, starts, 1.7) == "bench.sleep"

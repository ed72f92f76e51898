"""The readers of the program's own spans (bench/program_spans.py and
the four metric files that call it), on made-up ring events, and once on
the program's ring filled by a traced run of the harness on the CPU."""

import time

import pytest

from bench import harness, program_spans
from bench.driver import RunRecord
from bench.tests.smoke import PEAKS, small_cell

MS = 1_000_000
READERS = ("logits_to_host_ms.open", "host_step_ms.open",
           "logits_host_mb_per_step.open", "admit_wait_p90_ms.open")


def run(window=(10.0, 20.0)):
    return RunRecord(reqs=[], steps=[], window=window, due_window=window,
                     gave_up=30.0, late_s=[], prefix_before={},
                     prefix_after={}, admitted_prompt_tokens=0,
                     waiting_at_open=0, waiting_at_close=0,
                     compiles_in_window=0, open_loop=True)


def step(t0_ms, width, *, wait_ms=100, host_ms=300, nbytes=1000,
         publish_ms=0):
    """One engine step's spans as the ring holds them, starting at
    ``t0_ms`` (ms on the host clock): 2 ms of admit and stage, 1 ms of
    dispatch, then the wait, the logits copy and 3 ms of sampling."""
    t = t0_ms * MS
    spans = [("serve.admit", t, t + MS, {"step": 0}),
             ("serve.stage", t + MS, t + 2 * MS, {"step": 0}),
             ("serve.dispatch", t + 2 * MS, t + 3 * MS, {"step": 0})]
    t3 = t + 3 * MS
    t4 = t3 + wait_ms * MS
    t5 = t4 + host_ms * MS
    t6 = t5 + 3 * MS
    spans += [("serve.device_wait", t3, t4, {"step": 0}),
              ("serve.logits_to_host", t4, t5, {"step": 0, "bytes": nbytes}),
              ("serve.sample", t5, t6, {"step": 0})]
    if publish_ms:
        spans.append(("serve.publish", t5, t5 + publish_ms * MS,
                      {"step": 0, "rid": 1}))
    spans.append(("serve.step", t, t6, {"step": 0, "width": width}))
    return spans


def queued(t0_ms, t1_ms):
    return ("serve.queued", t0_ms * MS, t1_ms * MS, {"step": 0, "rid": 0})


def test_window_clips_steps_and_queue_waits():
    spans = (step(9_990, 128, host_ms=900)          # starts before the open
             + step(12_000, 128, host_ms=300)
             + step(19_990, 128, host_ms=700)       # ends after the close
             + [queued(9_000, 9_500), queued(9_500, 10_500),
                queued(18_000, 21_000)])
    r = run()
    assert program_spans.logits_to_host_ms(r, spans) == pytest.approx(300)
    assert program_spans.host_step_ms(r, spans) == pytest.approx(6)
    assert program_spans.admit_wait_p90_ms(r, spans) == pytest.approx(1000)


def test_decode_steps_are_left_out():
    spans = (step(11_000, 128, wait_ms=100, host_ms=300, nbytes=2000)
             + step(12_000, 1, wait_ms=40, host_ms=1, nbytes=16)
             + step(13_000, 128, wait_ms=100, host_ms=500, nbytes=2000))
    r = run()
    assert program_spans.logits_to_host_ms(r, spans) == pytest.approx(400)
    assert program_spans.logits_host_mb_per_step(r, spans) == \
        pytest.approx(0.002)
    assert len(program_spans.wide_steps(
        program_spans.window_spans(r, spans))) == 2


def test_self_time_leaves_out_the_nested_publish():
    spans = step(11_000, 128, publish_ms=2)
    (s,) = program_spans.wide_steps(program_spans.window_spans(run(), spans))
    assert s["ns"]["serve.sample"] == 3 * MS
    assert s["self_ns"]["serve.sample"] == pytest.approx(1 * MS)
    assert s["self_ns"]["serve.publish"] == pytest.approx(2 * MS)
    # the step's own time is what no phase covers: none here
    assert s["self_ns"]["serve.step"] == pytest.approx(0)
    # the engine's host work counts the publish once, inside the sample
    assert program_spans.host_step_ms(run(), spans) == pytest.approx(6)


def test_nothing_to_read_is_none(monkeypatch):
    r = run()
    for fn in (program_spans.logits_to_host_ms, program_spans.host_step_ms,
               program_spans.logits_host_mb_per_step,
               program_spans.admit_wait_p90_ms):
        assert fn(r, []) is None
        assert fn(r, step(12_000, 1)) is None    # a decode step alone
    # a program that keeps no ring: every reader file reads nothing
    import repro.obs.trace
    monkeypatch.delattr(repro.obs.trace, "program_tracer")
    assert program_spans.ring() == []
    for name in READERS:
        mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py")
        assert mod.read(r) is None


def test_logits_bytes_per_step_at_the_cells_widths(monkeypatch):
    """16 slots x 128 positions x 49152 vocabulary x 2 bytes (bf16)."""
    serving = harness.load_cell("starcoder2-7b.code_complete").config
    B = serving["serving"]["max_batch"]
    C = serving["serving"]["page_tokens"]
    V = serving["vocab_size"]
    nbytes = B * C * V * 2
    assert nbytes == 201_326_592
    spans = step(11_000, C, nbytes=nbytes) + step(12_000, C, nbytes=nbytes)
    monkeypatch.setattr(program_spans, "ring", lambda: spans)
    mod = harness.load_module(
        harness.BENCH / "metrics" / "logits_host_mb_per_step.open.py")
    assert mod.read(run()) == pytest.approx(201.326592)


def test_traced_harness_run_reads_the_programs_spans(monkeypatch):
    """The harness's traced window is a profiler session, so the engine
    fills the ring, and the four readers find their spans in it."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    cell = small_cell("starcoder2-7b.code_complete")
    res = harness.run(cell, 2**31 + 7, 1.5, True,
                      t_process0=time.perf_counter(), peaks=PEAKS)
    assert res["correct"], res["limits"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(READERS) <= set(got)
    s = cell.config["serving"]
    # an all-greedy step copies the token ids, B x C int32
    assert got["logits_host_mb_per_step.open"] == pytest.approx(
        s["max_batch"] * s["page_tokens"] * 4 / 1e6)
    assert got["logits_to_host_ms.open"] > 0
    assert got["host_step_ms.open"] > 0
    assert got["admit_wait_p90_ms.open"] >= 0

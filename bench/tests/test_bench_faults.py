"""The comparison that decides ``correct`` fails a broken timed path.

Each test breaks the program underneath the harness, the way a faulty
change to it would, then drives a whole run (only the look for a chip is
left out) and sees ``correct`` come out false.  The faults a one-chip
serving cell can have: a step that returns its state unchanged, half of
the batch left out of the step's result, and a token altered where it is
produced.  (A one-chip cell has no exchange between chips to leave out.)
"""

import time

import jax.numpy as jnp

import repro.models
import repro.serve.engine
from bench import harness
from bench.tests.smoke import PEAKS, small_cell

CELL = "starcoder2-7b.code_complete"


def run(seed=11, **kw):
    # arrivals fast enough that every slot serves requests in the window,
    # as all 16 do at the cell's own size
    cell = small_cell(CELL)
    cell.traffic = dict(cell.traffic, arrival=dict(cell.traffic["arrival"],
                                                   rate_per_s=16.0))
    return harness.run(cell, seed, 1.5, False,
                       t_process0=time.perf_counter(), peaks=PEAKS, **kw)


def broken_model(monkeypatch, wrap):
    build = repro.models.build_model

    def build_broken(cfg):
        api = build(cfg)
        step = api.serve_step
        object.__setattr__(api, "serve_step", wrap(step))
        return api

    monkeypatch.setattr(repro.models, "build_model", build_broken)


def test_sound_path_is_correct(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    assert run()["correct"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    broken_model(monkeypatch, lambda step: lambda p, t, c, n:
                 (step(p, t, c, n)[0], c))
    res = run()
    assert not res["correct"], res["limits"]


def test_half_the_batch_left_out(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")

    def wrap(step):
        def half(p, t, c, n):
            logits, caches = step(p, t, c, n)
            keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
            return jnp.where(keep[:, None, None], logits, 0), caches
        return half
    broken_model(monkeypatch, wrap)
    res = run()
    assert not res["correct"], res["limits"]


def test_token_altered_where_it_is_produced(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    sample = repro.serve.engine.ServingEngine._sample

    def altered(self, row, sp=repro.serve.engine.GREEDY):
        return (sample(self, row, sp) + 1) % len(row)
    monkeypatch.setattr(repro.serve.engine.ServingEngine, "_sample", altered)
    res = run()
    assert not res["correct"], res["limits"]

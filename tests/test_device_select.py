"""Token selection on the device (DESIGN.md §3.4): the engine copies the
step's argmax ids to the host, and a logits row only for a request that
samples stochastically.  The tokens it serves must be exactly those the
host sampler ``ServingEngine._sample`` picks over the full logits."""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.spec import init_params
from repro.obs import Obs
from repro.serve import SamplingParams, ServingEngine, SpecConfig
from repro.serve.engine import GREEDY, select_tokens

REPEAT = [5, 6, 7, 8, 9, 10, 11, 12, 13] * 3
HOT = SamplingParams(temperature=0.8, top_k=8)
SEED = 7


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    return cfg, api, params


def recording_engine(api, params, **kw):
    """An engine whose step records its full logits, its ``n_new`` and,
    in the engine's feed order, each participant with its output length
    and prompt cursor before the step."""
    jitted = jax.jit(api.serve_step)
    steps = []
    holder = {}

    def step_fn(p, tokens, caches, n_new):
        logits, caches = jitted(p, tokens, caches, n_new)
        n = np.asarray(n_new)
        steps.append({
            "logits": np.asarray(logits), "n_new": n,
            "feeds": [(slot, req, len(req.output), req.prompt_pos)
                      for slot, req in holder["engine"].active.items()
                      if n[slot]]})
        return logits, caches

    eng = ServingEngine(api, params, max_batch=4, max_seq=96, page_tokens=8,
                        seed=SEED, step_fn=step_fn, **kw)
    holder["engine"] = eng
    return eng, steps


def check_replay(steps) -> int:
    """Recompute every emitted token with ``_sample`` over the recorded
    logits and compare: a feed that ends its prompt emits from its last
    valid position, a decode or speculative feed from its positions in
    order; stochastic rows draw from a sampler seeded as the engine's, in
    the engine's feed order.  Returns the tokens compared."""
    ref = SimpleNamespace(rng=np.random.default_rng(SEED))
    compared = 0
    for st in steps:
        for slot, req, before, pos in st["feeds"]:
            n = int(st["n_new"][slot])
            decoding = pos >= len(req.prompt)
            if not decoding and pos + n < len(req.prompt):
                continue                     # mid-prompt: nothing emitted
            # the request's tokens up to its next feed (or its end)
            later = [b for s in steps for sl, r, b, _ in s["feeds"]
                     if r is req and b > before]
            end = min(later) if later else len(req.output)
            emitted = req.output[before:end]
            assert emitted, (req.rid, before)
            if not decoding or n == 1:
                assert len(emitted) == 1
                rows = [st["logits"][slot, n - 1]]
            else:                            # speculative: greedy only
                assert req.sampling.greedy and len(emitted) <= n
                rows = [st["logits"][slot, i] for i in range(len(emitted))]
            want = [ServingEngine._sample(ref, row, req.sampling)
                    for row in rows]
            assert emitted == want, (req.rid, before, emitted, want)
            compared += len(emitted)
    return compared


MIXES = {
    "greedy": [dict(), dict(), dict(), dict(), dict()],
    "spec": [dict(spec=SpecConfig(k=4))] * 3 + [dict()],
    "mixed": [dict(spec=SpecConfig(k=4)), dict(sampling=HOT), dict(),
              dict(sampling=HOT), dict(spec=SpecConfig(k=4)),
              dict(sampling=HOT)],
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_served_tokens_equal_host_sampler_over_full_logits(qwen, mix):
    cfg, api, params = qwen
    eng, steps = recording_engine(api, params)
    reqs = []
    for i, kw in enumerate(MIXES[mix]):
        prompt = REPEAT[:11 + 5 * i] if "spec" in kw \
            else list(range(1 + i, 14 + 4 * i))
        reqs.append(eng.submit(prompt, max_new_tokens=9 + i, **kw))
    eng.run_until_done()
    assert all(r.done and not r.truncated for r in reqs)
    assert check_replay(steps) == sum(len(r.output) for r in reqs)
    if mix != "greedy":
        assert eng.spec_steps > 0
    if mix == "mixed":
        # the stochastic requests ran alongside greedy ones in one step
        assert any({r.sampling.greedy for _, r, _, _ in st["feeds"]}
                   == {True, False} for st in steps)


def test_device_argmax_tie_goes_to_the_lowest_id():
    """A bf16 row with a three-way tie (one of them only after rounding
    to bf16): the device picks the lowest id, as ``_sample`` does."""
    row = np.zeros(64, np.float32)
    row[[5, 40]] = 3.0
    row[17] = 3.0 + 2.0 ** -9            # the f32 max; bf16 rounds it to 3
    dev = jnp.asarray(row, jnp.bfloat16)
    host = np.asarray(dev)
    assert (host == host.max()).nonzero()[0].tolist() == [5, 17, 40]
    ids, last = select_tokens(dev[None, None, :], jnp.array([1], jnp.int32))
    assert ids.dtype == jnp.int32 and int(ids[0, 0]) == 5
    assert ServingEngine._sample(None, host, GREEDY) == 5
    np.testing.assert_array_equal(np.asarray(last[0]), host)


def test_last_row_is_each_slots_last_valid_position():
    logits = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 32))
    ids, last = select_tokens(logits, jnp.array([2, 0, 4], jnp.int32))
    x = np.asarray(logits)
    np.testing.assert_array_equal(np.asarray(ids), x.argmax(-1))
    np.testing.assert_array_equal(np.asarray(last),
                                  np.stack([x[0, 1], x[1, 0], x[2, 3]]))


@pytest.mark.parametrize("hot", [False, True])
def test_selection_counters(qwen, hot):
    """``host_sampled_rows`` counts the rows the host sampled, one per
    token of a stochastic request, and stays 0 for an all-greedy run;
    ``device_selected_tokens`` counts the tokens taken from the ids."""
    cfg, api, params = qwen
    obs = Obs()
    eng = ServingEngine(api, params, max_batch=4, max_seq=96, page_tokens=8,
                        seed=SEED, obs=obs)
    kinds = [GREEDY, HOT if hot else GREEDY, GREEDY, HOT if hot else GREEDY]
    reqs = [eng.submit(list(range(2, 12 + 3 * i)), max_new_tokens=4 + i,
                       sampling=sp) for i, sp in enumerate(kinds)]
    eng.run_until_done()
    greedy = sum(len(r.output) for r in reqs if r.sampling.greedy)
    sampled = sum(len(r.output) for r in reqs if not r.sampling.greedy)
    assert (sampled > 0) == hot
    snap = obs.stats()["counters"]
    assert snap["engine.device_selected_tokens"] == greedy > 0
    assert snap["engine.host_sampled_rows"] == sampled
    assert (eng.device_selected_tokens, eng.host_sampled_rows) \
        == (greedy, sampled)

"""Tiered KV-page store (DESIGN.md §8a): host-memory cold tier below the
device pool.

Covers the allocator primitives (FreeList / HostArena), the demote ->
re-admit -> promote byte round trip, the backpressure ladder (demote
before destructive forget), staged-adoption publish ordering (STRICT
crash replay must be byte-identical with and without the tier), the
``pool_pages`` metadata cap, the promote-span overlap proof, and
refcount/pin invariants under random interleavings (hypothesis property
plus a deterministic companion that always runs)."""

import json

import jax
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.configs import get_config
from repro.core import FreeList, HostArena, HostTier, PMDevice
from repro.core.kvcache import replay_kv_commits
from repro.core.modes import Mode
from repro.core.oplog import OpLog
from repro.models import build_model
from repro.models.spec import init_params
from repro.obs import Obs, validate_chrome_trace
from repro.serve import ServeClient, ServingEngine


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    return cfg, api, params


def _page_bytes(eng, page):
    """Concatenated bytes of one physical device page across every layer
    pool (the engine's own deterministic gather order)."""
    return np.concatenate([np.asarray(v).ravel()
                           for v in eng._gather_page(page)])


def _audit(eng):
    """Cross-check the metadata planes against each other: controller
    refcounts must equal live-sequence links plus trie device pins, the
    trie's pin count must match its device-resident nodes, and the tier's
    occupancy must match the trie's host-resident nodes."""
    ctrl = eng.controller
    expect = np.zeros_like(ctrl._refcount)
    for seq in ctrl._seqs.values():
        for p in seq.pages:
            expect[p] += 1
    pc = eng.prefix_cache
    device_pins = 0
    if pc is not None:
        for node in pc._iter_nodes():
            if node.on_host:
                continue
            expect[node.page] += 1
            device_pins += 1
        assert device_pins == pc.pinned_pages
        if eng.tier is not None:
            assert pc.host_nodes == eng.tier.host_pages
    assert list(expect[1:]) == list(ctrl._refcount[1:]), \
        "refcounts drifted from seq links + trie pins"


# ------------------------------------------------------------ allocator


def test_freelist_recycles_and_guards_double_free():
    fl = FreeList(4)
    ids = [fl.alloc() for _ in range(4)]
    assert ids == [0, 1, 2, 3] and fl.full and fl.alloc() is None
    fl.free(1)
    assert not fl.full and fl.alloc() == 1      # FIFO recycle, not a bump
    fl.free(3)
    with pytest.raises(ValueError):
        fl.free(3)                               # double free
    with pytest.raises(ValueError):
        fl.free(99)                              # never allocated
    assert fl.alloc() == 3 and fl.in_use == 4


def test_host_arena_round_trips_bytes_and_reuses_regions():
    rng = np.random.default_rng(0)
    views = lambda: [rng.standard_normal((2, 4)).astype(np.float32),
                     rng.standard_normal((3,)).astype(np.float32)]
    arena = HostArena(capacity_pages=4, chunk_pages=2)
    stash = {}
    for _ in range(4):
        v = views()
        slot = arena.put(v)
        stash[slot] = [x.copy() for x in v]
    assert arena.full and arena.put(views()) is None
    assert arena.regions_created == 2           # 4 pages / chunk_pages=2
    for slot, want in stash.items():
        got = arena.get(slot)
        assert all(np.array_equal(a, b) for a, b in zip(got, want))
    for slot in stash:
        arena.free(slot)
    # refill: slots recycle in place, no new regions
    for _ in range(4):
        assert arena.put(views()) is not None
    assert arena.regions_created == 2 and arena.region_reuses > 0


def test_host_tier_demote_promote_callbacks():
    store = {7: [np.arange(6, dtype=np.float32).reshape(2, 3)]}
    writes = {}
    tier = HostTier(2, read_page=lambda p: store[p],
                    write_page=lambda v, p: writes.__setitem__(
                        p, [x.copy() for x in v]))
    slot = tier.demote(7)
    assert slot is not None and tier.host_pages == 1
    tier.promote(slot, 9)
    tier.free(slot)
    assert np.array_equal(writes[9][0], store[7][0])
    assert tier.pages_demoted == 1 and tier.pages_promoted == 1
    assert tier.host_pages == 0 and tier.host_drops == 0
    # a drop (eviction of a host leaf) is accounted separately
    s2 = tier.demote(7)
    tier.free(s2, promoted=False)
    assert tier.host_drops == 1


# ------------------------------------------------ demote/promote round trip


def test_evicted_then_readmitted_chain_is_byte_identical(qwen):
    """THE tier regression: release() spills an idle published chain to
    host, a later admission promotes it back, and the promoted device
    pages carry byte-identical KV."""
    cfg, api, params = qwen
    eng = ServingEngine(api, params, max_batch=2, max_seq=64, page_tokens=8,
                        host_cache_pages=8, prefix_cache=True)
    prompt = list(range(5, 22))                  # 2 full pages + tail
    req = eng.submit(prompt, max_new_tokens=2)
    eng.run_until_done()
    pc = eng.prefix_cache
    chain, n_tok = pc.match_links(prompt)
    assert n_tok >= 16 and not any(nd.on_host for nd in chain)
    before = {i: _page_bytes(eng, nd.page) for i, nd in enumerate(chain)}

    demoted = pc.release(pc.pinned_pages)        # spill everything idle
    assert demoted >= 2 and eng.tier.host_pages >= 2
    chain2, _ = pc.match_links(prompt)
    assert any(nd.on_host for nd in chain2), "chain did not stay matchable"

    req2 = eng.submit(prompt, max_new_tokens=2)
    eng.run_until_done()
    assert req2.prefix_tokens >= 16, "host-resident chain missed"
    assert eng.tier.pages_promoted >= 2
    chain3, _ = pc.match_links(prompt)
    for i, nd in enumerate(chain3[:len(before)]):
        assert not nd.on_host
        assert np.array_equal(_page_bytes(eng, nd.page), before[i]), \
            f"page {i} bytes changed across the tier round trip"
    assert req.output == req2.output
    _audit(eng)


def test_tier_outputs_identical_and_hits_recovered(qwen):
    """Tier on vs off, same capped pool, same prompts: identical greedy
    outputs, and only the tiered engine re-hits evicted chains."""
    cfg, api, params = qwen
    fam = np.random.default_rng(3)
    shared = [list(fam.integers(1, cfg.vocab, 16)) for _ in range(4)]
    prompts = [s + list(fam.integers(1, cfg.vocab, 8))
               for _ in range(2) for s in shared]
    outs, hits = [], []
    for host_pages in (16, 0):
        client = ServeClient(api, params, max_batch=2, max_seq=64,
                             page_tokens=8, pool_pages=7,
                             host_cache_pages=host_pages, prefix_cache=True)
        sess = client.open_session()
        got = []
        for p in prompts:
            r = sess.submit(p, max_new_tokens=3)
            client.run_until_done()
            got.append(r.output)
        outs.append(got)
        hits.append(client.engine.prefix_cache.hits)
        _audit(client.engine)
    assert outs[0] == outs[1], "host tier changed greedy outputs"
    assert hits[0] > 0 and hits[0] >= 2 * hits[1], \
        "tier recovered no evicted chains"


def test_release_ladder_demotes_before_forgetting(qwen):
    """With a tier, release() spills idle chains (non-destructive — they
    stay matchable); without one it falls back to destructive eviction."""
    cfg, api, params = qwen
    for host_pages in (8, 0):
        eng = ServingEngine(api, params, max_batch=1, max_seq=64,
                            page_tokens=8, host_cache_pages=host_pages, prefix_cache=True)
        prompt = list(range(30, 47))
        eng.submit(prompt, max_new_tokens=2)
        eng.run_until_done()
        pc = eng.prefix_cache
        freed = pc.release(pc.pinned_pages)
        assert freed >= 2
        _, n_tok = pc.match_links(prompt)
        if host_pages:
            assert pc.demotions >= 2 and n_tok >= 16
        else:
            assert pc.demotions == 0 and n_tok == 0
        _audit(eng)


def test_host_leaf_dropped_when_arena_full(qwen):
    """Arena exhaustion inside the ladder drops the LRU host leaf (loss-
    tolerant tier) rather than wedging release()."""
    cfg, api, params = qwen
    eng = ServingEngine(api, params, max_batch=1, max_seq=64, page_tokens=8,
                        host_cache_pages=2, prefix_cache=True)
    pc = eng.prefix_cache
    for base in (50, 100):
        eng.submit(list(range(base, base + 17)), max_new_tokens=2)
        eng.run_until_done()
        pc.release(pc.pinned_pages)
    assert eng.tier.host_pages <= 2
    assert eng.tier.host_drops + eng.tier.demote_failures > 0
    _audit(eng)


# ------------------------------------------------------------ pool capping


def test_pool_pages_caps_metadata_not_device_arrays(qwen):
    """``pool_pages`` shrinks only the controller's free list: device
    arrays keep the full geometry, and admission beyond the cap hits the
    backpressure ladder instead of OOM."""
    cfg, api, params = qwen
    full = ServingEngine(api, params, max_batch=2, max_seq=64, page_tokens=8)
    capped = ServingEngine(api, params, max_batch=2, max_seq=64,
                           page_tokens=8, pool_pages=5)
    assert capped.controller.geom.num_pages == 5
    for a, b in zip(full._pool_leaves(), capped._pool_leaves()):
        assert a.shape == b.shape, "pool cap resized device arrays"
    assert capped.controller.num_free_pages == 4
    req = capped.submit(list(range(5, 30)), max_new_tokens=8)
    capped.run_until_done()
    assert req.done        # served within the cap (possibly truncated)


# ------------------------------------------------- publish ordering / STRICT


def test_strict_replay_byte_identical_with_and_without_tier(qwen):
    """Crash replay of the oplog must rebuild the SAME committed extents
    whether a chain was adopted from device pages or promoted from host —
    the tier is never a durability participant, and ``finish_adopt``
    publishes the staged remainder only at flip time."""
    cfg, api, params = qwen
    maps = []
    for host_pages in (8, 0):
        dev = PMDevice(size=4 * 1024 * 1024)
        log = OpLog(dev, base_block=1, num_blocks=16)
        eng = ServingEngine(api, params, max_batch=1, max_seq=64,
                            page_tokens=8, oplog=log, mode=Mode.STRICT,
                            host_cache_pages=host_pages, prefix_cache=True)
        prompt = list(range(60, 77))
        eng.submit(prompt, max_new_tokens=2, mode=Mode.STRICT)
        eng.run_until_done()
        if host_pages:
            eng.prefix_cache.release(eng.prefix_cache.pinned_pages)
        req = eng.submit(prompt, max_new_tokens=2, mode=Mode.STRICT)
        eng.run_until_done()
        assert req.prefix_tokens >= 16
        if host_pages:
            assert eng.tier.pages_promoted >= 2
        replayed = replay_kv_commits(log.scan())
        # normalize: logical index -> page CONTENT hash (physical ids
        # legitimately differ; promoted chains land on fresh pages)
        m = {}
        for sid, extents in replayed.items():
            m[sid] = {i: _page_bytes(eng, p).tobytes()
                      for i, p in extents.items()}
        maps.append(m)
        _audit(eng)
    on, off = maps
    assert len(on) == len(off)
    for (son, eon), (soff, eoff) in zip(sorted(on.items()),
                                        sorted(off.items())):
        assert set(eon) == set(eoff), "committed extent indices differ"
        for i in eon:
            assert eon[i] == eoff[i], \
                f"sid {son}/{soff} page {i}: replayed bytes differ"


def test_staged_adoption_crash_before_flip_replays_to_prefix(qwen):
    """A crash between ``adopt_prefix_staged`` and ``finish_adopt`` must
    replay to a committed PREFIX of the chain: only the leading all-device
    run is logged at stage time; host-backed pages commit at the flip."""
    cfg, api, params = qwen
    dev = PMDevice(size=4 * 1024 * 1024)
    log = OpLog(dev, base_block=1, num_blocks=16)
    eng = ServingEngine(api, params, max_batch=2, max_seq=64, page_tokens=8,
                        oplog=log, mode=Mode.STRICT, host_cache_pages=8, prefix_cache=True)
    prompt = list(range(80, 97))
    eng.submit(prompt, max_new_tokens=2, mode=Mode.STRICT)
    eng.run_until_done()
    pc = eng.prefix_cache
    # demote only the DEEPEST page so the chain is device,device,host
    chain, _ = pc.match_links(prompt)
    deep = chain[-1]
    assert pc._demote(deep) and deep.on_host
    entries_before = len(list(log.scan()))

    req = eng.submit(prompt, max_new_tokens=2, mode=Mode.STRICT)
    # admit WITHOUT stepping: a lone promoting request would flip on the
    # step's feeds-empty path, hiding the staged (pre-flip) log state
    eng._admit()
    assert req.promoting
    mid = replay_kv_commits(log.scan())
    staged = mid.get(req.seq_id, {})
    assert sorted(staged) == [0], \
        "stage time must commit exactly the leading device run"
    eng.step()                               # flip lands, remainder commits
    assert not req.promoting
    after = replay_kv_commits(log.scan())[req.seq_id]
    assert sorted(after) == [0, 1], "flip did not publish the remainder"
    assert len(list(log.scan())) > entries_before
    eng.run_until_done()
    _audit(eng)


# ---------------------------------------------------------- overlap proof


def test_promote_span_overlaps_serve_step(qwen):
    """The acceptance criterion for async promotion: the [enqueue -> flip]
    span on the 200+ lane overlaps a serve.dispatch span (the step's
    enqueue) on the engine lane, and the trace still validates (nesting
    is per-tid)."""
    cfg, api, params = qwen
    obs = Obs(trace=True)
    eng = ServingEngine(api, params, max_batch=2, max_seq=64, page_tokens=8,
                        host_cache_pages=8, prefix_cache=True, obs=obs)
    shared = list(range(5, 22))
    eng.submit(shared, max_new_tokens=2)
    eng.run_until_done()
    eng.prefix_cache.release(eng.prefix_cache.pinned_pages)
    # a filler request keeps the engine busy so the flip lands MID-step
    eng.submit(list(range(200, 212)), max_new_tokens=6)
    eng.submit(shared + [3, 2, 1], max_new_tokens=2)
    eng.run_until_done()
    doc = obs.tracer.to_chrome()
    assert validate_chrome_trace(doc) == []
    evs = doc["traceEvents"]
    promotes = [e for e in evs if e["name"] == "promote"]
    steps = [e for e in evs if e["name"] == "serve.dispatch"]
    assert promotes and steps
    assert all(e["tid"] >= 200 for e in promotes)
    def span(e):
        return e["ts"], e["ts"] + e["dur"]
    overlapped = [p for p in promotes if p["args"]["overlapped"]]
    assert overlapped, "no promotion landed while the engine was stepping"
    p0, p1 = span(overlapped[0])
    assert any(s0 < p1 and p0 < s1 for s0, s1 in map(span, steps)), \
        "promote span does not overlap any serve.dispatch span"
    assert json.dumps(doc)                   # serializable end to end
    demotes = [e for e in evs if e["name"] == "demote"]
    assert demotes and all(e["tid"] == 2 for e in demotes)


def test_promote_lag_metric_in_profiler_window(qwen):
    """obs plumbing: tier counters register, and the windowed profiler
    derives promote_lag_ms from the window's counter deltas."""
    cfg, api, params = qwen
    obs = Obs()
    eng = ServingEngine(api, params, max_batch=1, max_seq=64, page_tokens=8,
                        host_cache_pages=8, prefix_cache=True, obs=obs)
    prompt = list(range(5, 22))
    eng.submit(prompt, max_new_tokens=2)
    eng.run_until_done()
    eng.prefix_cache.release(eng.prefix_cache.pinned_pages)
    eng.submit(prompt, max_new_tokens=2)
    eng.run_until_done()
    snap = obs.registry.snapshot()
    assert snap["tier.pages_demoted"] >= 2
    assert snap["tier.pages_promoted"] >= 2
    assert snap["tier.promotes"] >= 1
    assert snap["kv.host_capacity"] == 8
    obs.profiler.flush()
    w = obs.profiler.windows()[-1]
    assert w.promote_lag_ms > 0
    assert w.as_dict()["promote_lag_ms"] == round(w.promote_lag_ms, 3)


# ----------------------------------------------------- interleaving audit


def _interleave(eng, ops, prompts):
    """Apply an op sequence against a tiered engine, auditing invariants
    after every op.  Ops: 0=submit+run, 1=release(spill), 2=readmit the
    oldest prompt, 3=clear the trie."""
    pc = eng.prefix_cache
    outs = {}
    for i, op in enumerate(ops):
        if op == 0:
            p = prompts[i % len(prompts)]
            r = eng.submit(p, max_new_tokens=2)
            eng.run_until_done()
            outs.setdefault(tuple(p), r.output)
            assert outs[tuple(p)] == r.output, \
                "same prompt, same greedy output — tier changed bytes"
        elif op == 1:
            pc.release(max(pc.pinned_pages, 1))
        elif op == 2:
            r = eng.submit(prompts[0], max_new_tokens=2)
            eng.run_until_done()
            want = outs.setdefault(tuple(prompts[0]), r.output)
            assert want == r.output
        elif op == 3:
            pc.clear()
            assert pc.pinned_pages == 0 and pc.host_nodes == 0
            assert eng.tier.host_pages == 0
        _audit(eng)


def _tier_engine(api, params):
    return ServingEngine(api, params, max_batch=2, max_seq=64, page_tokens=8,
                         host_cache_pages=6, prefix_cache=True)


def _tier_prompts(vocab):
    rng = np.random.default_rng(11)
    return [list(rng.integers(1, vocab, 17)) for _ in range(3)]


@given(ops=st.lists(st.integers(min_value=0, max_value=3), min_size=4,
                    max_size=10))
@settings(max_examples=15, deadline=None,
          suppress_health_check=[HealthCheck.too_slow])
def test_tier_interleavings_property(ops):
    """Random demote/promote/admit/clear interleavings keep every
    invariant (skips when hypothesis isn't installed — the deterministic
    companion below always runs)."""
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    _interleave(_tier_engine(api, params), ops, _tier_prompts(cfg.vocab))


def test_tier_interleavings_deterministic(qwen):
    """Deterministic companion to the hypothesis property: fixed op
    scripts covering demote-then-rehit, clear-with-host-residents, arena
    churn, and repeated spills."""
    cfg, api, params = qwen
    scripts = [
        [0, 1, 2, 0, 1, 2],            # spill / readmit cycles
        [0, 0, 0, 1, 1, 2, 0],         # multi-chain spill, partial promote
        [0, 1, 3, 0, 2],               # clear() with host residents
        [0, 1, 0, 1, 0, 1, 2, 2],      # arena churn (capacity 6)
    ]
    for ops in scripts:
        _interleave(_tier_engine(api, params), ops, _tier_prompts(cfg.vocab))

"""Observability plane (DESIGN.md §10): registry semantics, pool-gauge
accounting across admission/fork/CoW/evict interleavings, trie-hit vs
adoption agreement, Chrome-trace validity + span nesting, the windowed
profiler, the overhead ledger, fault-plane counters, and the
disabled-by-default zero-cost guarantee."""

import gc
import glob
import json
import time
import weakref

import jax
import pytest

from repro.configs import get_config
from repro.core.kvcache import KVGeometry, PagedKVCache
from repro.dist.fault import FaultPolicy, HeartbeatMonitor
from repro.models import build_model
from repro.models.spec import init_params
from repro.obs import (Obs, OverheadLedger, Registry, SpanTracer,
                       WindowedProfiler, attach_fault, program_tracer,
                       validate_chrome_trace)
from repro.serve import PrefixCache, ServeClient


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    return cfg, api, params


# ---------------------------------------------------------------- registry


def test_counters_are_monotonic():
    reg = Registry()
    c = reg.counter("events")
    c.inc()
    c.inc(3)
    assert reg.snapshot()["events"] == 4
    with pytest.raises(ValueError):
        c.inc(-1)                            # counters never go down
    assert reg.counter("events") is c        # get-or-create
    assert "events" in reg.monotonic_names()


def test_registry_kind_collisions_and_lazy():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")                       # cross-kind name collision
    with pytest.raises(ValueError):
        reg.register("x", lambda: 0)
    g = reg.gauge("depth")
    g.set(5)
    g.add(-2)
    box = {"v": 7}
    reg.register("lazy", lambda: box["v"], monotonic=True)
    snap = reg.snapshot()
    assert snap["depth"] == 3 and snap["lazy"] == 7
    box["v"] = 9
    assert reg.snapshot()["lazy"] == 9       # read at snapshot time
    # re-registering replaces the reader (engine rebuilt over one Obs)
    reg.register("lazy", lambda: 42, monotonic=True)
    assert reg.snapshot()["lazy"] == 42


# ---------------------------------------------------------------- pool gauge


def test_pool_gauge_matches_alloc_minus_freed_across_interleavings():
    """pages_in_use == pages_allocated - pages_freed through create /
    append / fork / CoW / adopt / evict / free, and the pool is whole
    once every reference is dropped."""
    kv = PagedKVCache(KVGeometry(num_pages=32, page_tokens=4, max_seqs=8,
                                 pages_per_seq=8))

    def check():
        assert kv.pages_in_use == kv.pages_allocated - kv.pages_freed

    a = kv.create_seq()
    kv.append_tokens(a, 10)                  # 2 full pages + tail
    check()
    b = kv.fork(a)                           # refcounted full pages
    check()
    assert kv.prepare_append(b, 1) is not None   # CoW tail copy
    check()
    kv.append_tokens(b, 3)
    check()

    pc = PrefixCache(kv)
    c = kv.create_seq()
    kv.append_tokens(c, 8)
    prompt = list(range(100, 108))
    pc.insert(prompt, kv.committed_extents(c))
    check()
    d = kv.create_seq()
    pages, n_tok = pc.match(prompt + [1], align=1)
    assert n_tok == 8
    kv.adopt_prefix(d, pages)                # shared, no fresh allocation
    check()

    for sid in (a, b, c):
        kv.free_seq(sid)
        check()
    pc.release(10)                           # evict idle pins
    check()
    kv.free_seq(d)
    check()
    pc.clear()
    check()
    assert kv.pages_in_use == 0
    assert kv.num_free_pages == 31           # whole pool minus null page


# ---------------------------------------------------------------- trie/adopt


def test_trie_hits_match_adoption_events(qwen):
    """Every trie hit is an adoption: pages_adopted == match_pages_sum,
    tokens_saved == adopted pages x page_tokens."""
    cfg, api, params = qwen
    obs = Obs()
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8,
                         obs=obs)
    sess = client.open_session()
    shared = list(range(1, 17))              # 2 full pages
    for tail in ([21, 22, 23], [31, 32, 33], [41, 42, 43]):
        sess.submit(shared + tail, max_new_tokens=2)
        client.run_until_done()
    snap = obs.registry.snapshot()
    assert snap["trie.hits"] == 2            # first ingest seeds the trie
    assert snap["trie.misses"] >= 1
    assert snap["kv.pages_adopted"] == snap["trie.match_pages_sum"] == 4
    assert snap["trie.tokens_saved"] == 4 * 8
    assert snap["trie.deepest_match"] == 2
    # all sequences freed: only cache pins hold pages now
    assert snap["kv.pages_in_use"] == snap["trie.pinned_pages"]
    client.engine.prefix_cache.clear()
    assert client.engine.controller.pages_in_use == 0


# ---------------------------------------------------------------- tracing


def test_trace_is_valid_chrome_and_spans_nest(qwen, tmp_path):
    cfg, api, params = qwen
    obs = Obs(trace=True)
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8,
                         obs=obs)
    sess = client.open_session()
    r1 = sess.submit(list(range(1, 20)), max_new_tokens=3)
    sess.submit(list(range(1, 12)), max_new_tokens=2)
    client.run_until_done()
    path = tmp_path / "trace.json"
    client.dump_trace(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) == []
    names = {ev["name"] for ev in doc["traceEvents"]}
    for expected in ("serve.step", "serve.admit", "serve.stage",
                     "serve.sync_page_table", "serve.dispatch",
                     "serve.device_wait", "serve.logits_to_host",
                     "serve.sample", "serve.queued", "submit",
                     f"req{r1.rid}"):
        assert expected in names, expected
    # request lifetimes live on their own slot lanes, with the ledger
    req_evs = [ev for ev in doc["traceEvents"] if ev.get("tid", 0) >= 100]
    assert req_evs and all(ev["args"]["steps"] > 0 for ev in req_evs)
    assert doc["otherData"]["dropped_events"] == 0


def test_trace_disabled_adds_zero_entries(qwen):
    """obs=None and Obs(trace=False) both keep the trace empty; only
    Obs(trace=True) records."""
    cfg, api, params = qwen
    obs = Obs()                              # trace off: ledger only
    client = ServeClient(api, params, max_batch=1, max_seq=64, page_tokens=8,
                         obs=obs)
    sess = client.open_session()
    sess.submit([1, 2, 3, 4], max_new_tokens=2)
    client.run_until_done()
    assert obs.tracer is None
    assert "trace_events" not in obs.stats()
    with pytest.raises(ValueError):
        obs.dump_trace("/dev/null")
    # fully uninstrumented engine: no obs object at all, same outputs path
    bare = ServeClient(api, params, max_batch=1, max_seq=64, page_tokens=8)
    assert bare.engine.obs is None
    bsess = bare.open_session()
    bsess.submit([1, 2, 3, 4], max_new_tokens=2)
    bare.run_until_done()
    assert "obs" not in bare.stats()


def test_tracer_cap_and_validator_catches_overlap():
    tr = SpanTracer(max_events=2)
    tr.complete("a", "t", 0, 10)
    tr.complete("b", "t", 2, 8)
    tr.complete("c", "t", 20, 30)            # over cap: the oldest goes
    assert len(tr) == 2 and tr.dropped == 1
    assert [sp[0] for sp in tr.spans()] == ["b", "c"]
    assert tr.to_chrome()["otherData"]["dropped_events"] == 1
    bad = {"traceEvents": [
        {"name": "a", "cat": "t", "ph": "X", "ts": 0.0, "dur": 10.0,
         "pid": 0, "tid": 0},
        {"name": "b", "cat": "t", "ph": "X", "ts": 5.0, "dur": 10.0,
         "pid": 0, "tid": 0},               # straddles a's end: not nested
    ]}
    assert any("overlaps" in p for p in validate_chrome_trace(bad))
    assert validate_chrome_trace({"traceEvents": []})


# ---------------------------------------------------------------- step phases

PHASES = ("serve.dispatch", "serve.device_wait", "serve.logits_to_host",
          "serve.sample")
TAXONOMY = {"serve.step", "serve.admit", "serve.adopt_prefix",
            "serve.stage", "serve.draft", "serve.sync_page_table",
            "serve.dispatch", "serve.promote_flip", "serve.device_wait",
            "serve.logits_to_host", "serve.sample", "serve.publish",
            "serve.verify", "serve.rollback"}


def _profiled(tmp_path, fn):
    """Run ``fn`` inside a profiler session as the benchmark starts it;
    returns the raw perf_counter_ns bounds of the session."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.perf_counter_ns()
    try:
        fn()
    finally:
        t1 = time.perf_counter_ns()
        jax.profiler.stop_trace()
    return t0, t1


def _serve_two(client):
    sess = client.open_session()
    sess.submit(list(range(1, 20)), max_new_tokens=3)
    sess.submit(list(range(1, 12)), max_new_tokens=2, temperature=0.8,
                top_k=8)
    client.run_until_done()


def test_step_phases_are_profiler_spans_and_ring_entries(qwen, tmp_path):
    """Under a profiler session the engine's phases appear in the
    xplane's host plane under their exact names, nested in serve.step,
    and the in-memory ring holds the same spans on perf_counter_ns."""
    from jax.profiler import ProfileData

    cfg, api, params = qwen
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8)
    lo, hi = _profiled(tmp_path, lambda: _serve_two(client))
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    host = [(e.start_ns, e.end_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]
    assert {n for _, _, n in host} <= TAXONOMY
    doc = {"traceEvents": [{"name": n, "ph": "X", "ts": s / 1e3,
                            "dur": (e - s) / 1e3, "pid": 0, "tid": 0}
                           for s, e, n in host]}
    assert validate_chrome_trace(doc) == []
    steps = [(s, e) for s, e, n in host if n == "serve.step"]
    computed = 0
    for s0, s1 in steps:
        inside = sorted((s, n) for s, e, n in host
                        if s0 <= s and e <= s1 and n != "serve.step")
        if not any(n == "serve.dispatch" for _, n in inside):
            continue
        computed += 1
        order = [n for _, n in inside if n in PHASES]
        assert order == list(PHASES), order
    assert computed == client.engine.steps

    ring = [sp for sp in program_tracer().spans()
            if lo <= sp[1] and sp[2] <= hi and sp[0] != "serve.queued"]
    assert sorted(n for n, *_ in ring) == sorted(n for _, _, n in host)
    B, V = 2, cfg.vocab
    width = {a["step"]: a["width"] for n, _, _, a in ring
             if n == "serve.step" and "width" in a}
    copies = [a for n, _, _, a in ring if n == "serve.logits_to_host"]
    assert len(copies) == len(width) == computed
    # int32 ids of every position, plus each slot's last logits row on
    # the steps where the stochastic request samples on the host
    for a in copies:
        ids = B * width[a["step"]] * 4
        if a["rows"]:
            assert a["bytes"] - ids in (B * V * 2, B * V * 4)
        else:
            assert a["bytes"] == ids
    assert {a["rows"] for a in copies} == {0, 1}
    queued = [a["rid"] for n, _, t1, a in program_tracer().spans()
              if n == "serve.queued" and lo <= t1 <= hi]
    assert sorted(queued) == [0, 1]


def test_ring_stays_empty_without_a_session_or_obs(qwen):
    cfg, api, params = qwen
    ring = program_tracer()
    before = (len(ring), ring.dropped)
    _serve_two(ServeClient(api, params, max_batch=2, max_seq=64,
                           page_tokens=8))
    assert (len(ring), ring.dropped) == before


def test_ring_holds_no_reference_to_the_engine(qwen, tmp_path):
    """The benchmark frees the served model before its reference runs:
    the spans the ring keeps must not hold the engine alive."""
    cfg, api, params = qwen
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8)
    lo, hi = _profiled(tmp_path, lambda: _serve_two(client))
    engine = weakref.ref(client.engine)
    del client
    gc.collect()
    assert engine() is None
    assert any(lo <= t0 and t1 <= hi for _, t0, t1, _ in
               program_tracer().spans())


def test_ledger_split_comes_from_the_step_spans(qwen):
    """The overhead ledger reads the serve.* spans' stamps: its device
    bucket is serve.dispatch + serve.device_wait, and its three buckets
    sum to the computing steps' serve.step time."""
    cfg, api, params = qwen
    obs = Obs(trace=True)
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8,
                         obs=obs)
    _serve_two(client)
    spans = obs.tracer.spans()

    def total(*names):
        return sum(t1 - t0 for n, t0, t1, _ in spans if n in names)

    led = {c: obs.ledger.phase_totals("prefill")[c]
           + obs.ledger.phase_totals("decode")[c]
           for c in ("scheduler", "device", "persistence", "steps")}
    assert led["device"] == total("serve.dispatch", "serve.device_wait")
    assert led["steps"] == client.engine.steps
    stepped = sum(t1 - t0 for n, t0, t1, a in spans
                  if n == "serve.step" and "width" in a)
    assert led["scheduler"] + led["device"] + led["persistence"] == stepped


# ---------------------------------------------------------------- profiler


def test_profiler_windows_delta_counters_and_ring():
    reg = Registry()
    box = {"tok": 0, "occ": 0.0}
    reg.register("engine.tokens", lambda: box["tok"], monotonic=True)
    reg.register("occupancy", lambda: box["occ"])
    prof = WindowedProfiler(reg, window_s=1.0, capacity=2)
    prof.observe(now=0.0)                    # opens window, snapshots
    box["tok"], box["occ"] = 10, 0.5
    prof.observe(now=0.4)                    # inside the window: no close
    assert prof.windows() == []
    box["tok"], box["occ"] = 30, 0.75
    prof.observe(now=1.2)                    # boundary passed: closes
    (w,) = prof.windows()
    assert w.counters["engine.tokens"] == 30      # delta over the window
    assert w.gauges["occupancy"] == 0.75          # level at close
    assert w.t_start == 0.0 and w.t_end == 1.2
    assert w.tok_s == pytest.approx(30 / 1.2)
    box["tok"] = 40
    prof.observe(now=2.3)
    box["tok"] = 45
    prof.flush(now=2.5)                      # partial window closes too
    wins = prof.windows()
    assert len(wins) == 2                    # capacity=2: oldest fell off
    assert [w.index for w in wins] == [1, 2]
    assert wins[0].counters["engine.tokens"] == 10   # 30 -> 40
    assert wins[1].counters["engine.tokens"] == 5    # 40 -> 45
    assert wins[1].duration == pytest.approx(0.2)


# ---------------------------------------------------------------- ledger


def test_overhead_ledger_breakdown_shares():
    led = OverheadLedger()
    led.add("prefill", sched_ns=100, device_ns=800, persist_ns=100, steps=2)
    led.add("decode", sched_ns=50, device_ns=900, persist_ns=50, steps=5)
    led.add_client(1000)
    bd = led.breakdown()
    assert bd["phases"]["prefill"]["steps"] == 2
    pre = bd["phases"]["prefill"]["shares"]
    assert pre["device"] == pytest.approx(0.8)
    assert sum(pre.values()) == pytest.approx(1.0)
    total = bd["shares"]
    assert sum(total.values()) == pytest.approx(1.0)   # incl. client
    assert total["client"] == pytest.approx(1000 / 3000)
    assert bd["software_frac"] == pytest.approx(1.0 - total["device"])
    assert bd["total_s"] == pytest.approx(3000 / 1e9)
    led.reset()
    assert led.breakdown()["total_s"] == 0.0


def test_engine_ledger_sums_to_phase_totals(qwen):
    """Per-request ledgers (even split across each step's participants)
    sum to the engine's phase totals, and client_ns covers submit->admit."""
    cfg, api, params = qwen
    obs = Obs()
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8,
                         obs=obs)
    sess = client.open_session()
    reqs = [sess.submit(list(range(1, 10 + i)), max_new_tokens=3)
            for i in range(2)]
    client.run_until_done()
    totals = {c: obs.ledger.phase_totals("prefill")[c]
              + obs.ledger.phase_totals("decode")[c]
              for c in ("scheduler", "device", "persistence")}
    for comp, key in (("scheduler", "scheduler_ns"), ("device", "device_ns"),
                      ("persistence", "persistence_ns")):
        summed = sum(r.ledger[key] for r in reqs)
        # integer division during the even split loses < n_steps ns
        assert 0 <= totals[comp] - summed <= 2 * sum(
            r.ledger["steps"] for r in reqs)
    assert all(r.ledger["client_ns"] >= 0 for r in reqs)
    assert all(r.ledger["steps"] > 0 for r in reqs)


# ---------------------------------------------------------------- fault plane


def test_fault_counters_track_steals_and_remeshes():
    mon = HeartbeatMonitor([0, 1, 2], timeout_s=1.0, patience=1,
                           straggler_factor=1.5)
    pol = FaultPolicy(mon, assignment={0: 0, 1: 1}, spares=[2],
                      chips_per_worker=1, model_axis=1)
    obs = Obs()
    attach_fault(obs, pol)
    for w, st in ((0, 1.0), (1, 10.0), (2, 1.0)):
        mon.beat(w, step=1, step_time=st, now=0.0)
    plan = pol.poll(now=0.5)                 # straggler 1 -> spare 2 steals
    assert plan is not None and plan.straggler == 1
    snap = obs.registry.snapshot()
    assert snap["fault.heartbeats"] == 3
    assert snap["fault.steals"] == 1 and snap["fault.remeshes"] == 0
    assert snap["fault.straggler_flags"] == 1
    assert snap["fault.spares"] == 0
    # now the shard-owning worker 2 goes silent -> death -> remesh
    mon.beat(0, step=2, step_time=1.0, now=10.0)
    mon.beat(1, step=2, step_time=1.0, now=10.0)
    plan = pol.poll(now=10.0)
    assert plan is not None and plan.mesh_shape[-2] >= 1
    snap = obs.registry.snapshot()
    assert snap["fault.deaths"] == 1
    assert snap["fault.heartbeats_missed"] == 1
    assert snap["fault.remeshes"] == 1
    assert snap["fault.alive"] == 2


# ---------------------------------------------------------------- stats shape


def test_obs_stats_payload_shape(qwen):
    cfg, api, params = qwen
    obs = Obs(window_s=0.001)                # tiny windows: steps close them
    client = ServeClient(api, params, max_batch=1, max_seq=64, page_tokens=8,
                         obs=obs)
    sess = client.open_session()
    sess.submit(list(range(1, 18)), max_new_tokens=4)
    client.run_until_done()
    st = sess.stats()
    assert st["submitted"] == 1 and st["done"] == 1
    assert st["overhead_ns"]["steps"] > 0
    payload = st["engine"]
    assert set(payload) >= {"counters", "windows", "overhead"}
    assert payload["counters"]["engine.steps"] > 0
    assert payload["windows"], "profiler produced no windows"
    total_tok = sum(w["counters"]["engine.tokens"]
                    for w in payload["windows"])
    assert total_tok == payload["counters"]["engine.tokens"]
    assert payload["overhead"]["phases"]["decode"]["steps"] > 0

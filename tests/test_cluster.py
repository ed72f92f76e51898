"""Cluster plane (DESIGN.md §12): prefix-affinity routing, failure-atomic
session snapshot/restore on the controller (staged restore + flip under
all three consistency modes, crash-between replays the pre-restore
committed state), kill-one-engine / straggler-steal / remesh migration
with token identity, parked-restore draining, the fault ladder's
steal-on-death rung, and the byte tokenizer front."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import PMDevice
from repro.core.kvcache import (KVGeometry, KVPoolFullError, PagedKVCache,
                                replay_kv_commits)
from repro.core.modes import Mode
from repro.core.oplog import OpLog
from repro.dist.fault import (FaultPolicy, HeartbeatMonitor, RemeshPlan,
                              StealPlan)
from repro.models import build_model
from repro.models.spec import init_params
from repro.serve import (ByteTokenizer, EngineCluster, PrefixRouter,
                         ServeClient, prefix_hash)


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    return cfg, api, params


@pytest.fixture(scope="module")
def mamba():
    cfg = get_config("mamba2-1.3b", smoke=True)
    api = build_model(cfg)
    params = init_params(api.init_specs(), jax.random.PRNGKey(0))
    return cfg, api, params


def fresh_oplog():
    device = PMDevice(size=4 * 1024 * 1024)
    return device, OpLog(device, base_block=1, num_blocks=16)


def family_prompts(vocab: int, n: int, *, families: int = 2,
                   prefix_len: int = 16, seed: int = 7):
    """``n`` distinct prompts drawn from ``families`` shared prefixes —
    the affinity router's workload shape."""
    rng = np.random.default_rng(seed)
    heads = [list(rng.integers(1, vocab, prefix_len)) for _ in range(families)]
    return [heads[i % families] + list(rng.integers(1, vocab, 6 + i % 5))
            for i in range(n)]


# ------------------------------------------------------------------ router


def test_prefix_hash_affinity_and_determinism():
    a = [3, 1, 4, 1, 5, 9, 2, 6] * 4
    assert prefix_hash(a, 16) == prefix_hash(list(a), 16)
    # only the first k tokens matter: shared-prefix prompts share a home
    assert prefix_hash(a[:16] + [7, 7], 16) == prefix_hash(a[:16] + [8], 16)
    assert prefix_hash([1] + a[1:], 16) != prefix_hash(a, 16)


def test_router_spillover_hysteresis():
    r = PrefixRouter(2, prefix_tokens=4, spill_margin=3)
    p = [1, 2, 3, 4]
    home = prefix_hash(p, 4) % 2
    other = 1 - home
    # below the margin affinity wins, even when home is busier
    shard, spilled = r.route(p, {home: 2, other: 0})
    assert shard == home and not spilled
    # at the margin the session spills to the least-loaded shard
    shard, spilled = r.route(p, {home: 3, other: 0})
    assert shard == other and spilled
    assert r.stats() == {"n_shards": 2, "routed_home": 1, "spills": 1}


def test_router_survives_remesh_shrink():
    r = PrefixRouter(4, prefix_tokens=4, spill_margin=8)
    p = [9, 9, 9, 9]
    # mid-remesh: the home shard has no live engine; fall through to the
    # live set instead of KeyError'ing the submit path
    shard, _ = r.route(p, {0: 1, 2: 0})
    assert shard in (0, 2)
    r.n_shards = 1
    assert r.route([5], {0: 0})[0] == 0


def test_router_validation():
    with pytest.raises(ValueError):
        PrefixRouter(0)
    with pytest.raises(ValueError):
        PrefixRouter(2, spill_margin=0)


# ------------------------------- controller snapshot / restore round trip


@pytest.mark.parametrize("mode", [Mode.POSIX, Mode.SYNC, Mode.STRICT])
def test_snapshot_restore_staged_then_flip(mode):
    """The migration protocol at the controller: snapshot on the source,
    STAGE on the target (nothing published — a crash here replays the
    target to its PRE-restore committed state, never a torn session),
    then FLIP (publish + STRICT oplog in the target's own volume)."""
    geom = KVGeometry(num_pages=32, page_tokens=8, max_seqs=4,
                      pages_per_seq=8)
    _, src_log = fresh_oplog()
    _, tgt_log = fresh_oplog()
    src = PagedKVCache(geom, oplog=src_log)
    tgt = PagedKVCache(geom, oplog=tgt_log)

    sid = src.create_seq(mode)
    src.append_tokens(sid, 20)            # 2 full pages committed + tail
    snap = src.snapshot_seq(sid)
    assert snap.length == 20 and snap.committed_pages == 2
    assert len(snap.pages) == 3 and snap.mode is mode

    # pre-existing target state: a STRICT resident whose extents define
    # the pre-restore committed state crash replay must reproduce
    keep = tgt.create_seq(Mode.STRICT)
    tgt.append_tokens(keep, 8)
    replay_before = replay_kv_commits(tgt_log.scan())
    assert sorted(replay_before[keep]) == [0]

    in_use_before = tgt.pages_in_use
    rsid, pages = tgt.restore_seq_staged(snap)
    assert len(pages) == 3 and tgt.seq_length(rsid) == 20
    # staged, not published: no extents, no oplog entries -> a crash now
    # replays exactly the pre-restore state
    assert tgt.committed_extents(rsid) == {}
    assert replay_kv_commits(tgt_log.scan()) == replay_before

    assert tgt.restore_seq(rsid) == 2     # FLIP: both full pages publish
    assert tgt.committed_extents(rsid) == {0: pages[0], 1: pages[1]}
    replay_after = replay_kv_commits(tgt_log.scan())
    if mode.logs_ops:
        # the restored extent now replays from the TARGET's volume
        assert replay_after[rsid] == {0: pages[0], 1: pages[1]}
    else:
        # POSIX/SYNC migration writes nothing to the target's log
        assert replay_after == replay_before
    assert tgt.restore_seq(rsid) == 0     # flip is idempotent

    # the restored sequence decodes on: the tail fills and publishes
    tgt.advance(rsid, 4)
    assert tgt.seq_length(rsid) == 24
    assert sorted(tgt.committed_extents(rsid)) == [0, 1, 2]
    assert tgt.pages_in_use == in_use_before + 3


def test_staged_restore_capacity_failures_leak_nothing():
    geom = KVGeometry(num_pages=3, page_tokens=8, max_seqs=2,
                      pages_per_seq=8)
    src = PagedKVCache(KVGeometry(num_pages=8, page_tokens=8, max_seqs=2,
                                  pages_per_seq=8))
    sid = src.create_seq()
    src.append_tokens(sid, 24)            # 3 pages > the 2-page target pool
    snap = src.snapshot_seq(sid)
    tgt = PagedKVCache(geom)
    before = tgt.pages_in_use
    with pytest.raises(KVPoolFullError):
        tgt.restore_seq_staged(snap)
    assert tgt.pages_in_use == before, "failed stage leaked pages"


# ------------------------------------------- fault-ladder steal-on-death


def _dead_monitor(workers, dead, *, timeout=5.0):
    mon = HeartbeatMonitor(workers, timeout_s=timeout, patience=1,
                           straggler_factor=100.0)
    for w in workers:
        mon.beat(w, 0, 0.01, now=0.0)
    for w in workers:
        if w not in dead:
            mon.beat(w, 1, 0.01, now=timeout + 1.0)
    return mon


def test_policy_steals_dead_shard_to_spare():
    mon = _dead_monitor([0, 1, 2], dead={1})
    pol = FaultPolicy(mon, assignment={0: 0, 1: 1}, spares=[2],
                      chips_per_worker=1, model_axis=1, steal_on_death=True)
    plan = pol.poll(now=6.1)
    assert isinstance(plan, StealPlan)
    assert plan.straggler == 1 and plan.spare == 2 and plan.shard == 1
    assert pol.assignment == {0: 0, 2: 1} and pol.spares == []
    assert pol.steals == 1 and pol.remeshes == 0
    assert pol.poll(now=6.2) is None


def test_policy_death_without_spare_remeshes():
    mon = _dead_monitor([0, 1], dead={1})
    pol = FaultPolicy(mon, assignment={0: 0, 1: 1}, spares=[],
                      chips_per_worker=1, model_axis=1, steal_on_death=True)
    plan = pol.poll(now=6.1)
    assert isinstance(plan, RemeshPlan)
    assert plan.survivors == (0,) and pol.assignment == {0: 0}
    assert pol.remeshes == 1 and pol.steals == 0


def test_policy_default_death_skips_steal_rung():
    # training keeps the default: confirmed death => restore + reshard,
    # even with a spare free (the spare joins nothing mid-restore)
    mon = _dead_monitor([0, 1, 2], dead={1})
    pol = FaultPolicy(mon, assignment={0: 0, 1: 1}, spares=[2],
                      chips_per_worker=1, model_axis=1)
    plan = pol.poll(now=6.1)
    assert isinstance(plan, RemeshPlan) and pol.steals == 0


def test_policy_two_deaths_one_spare_escalates():
    mon = _dead_monitor([0, 1, 2, 3], dead={1, 2})
    pol = FaultPolicy(mon, assignment={0: 0, 1: 1, 2: 2}, spares=[3],
                      chips_per_worker=1, model_axis=1, steal_on_death=True)
    first = pol.poll(now=6.1)
    assert isinstance(first, StealPlan) and first.spare == 3
    # one plan per poll; the second dead shard finds no spare -> remesh
    second = pol.poll(now=6.2)
    assert isinstance(second, RemeshPlan)
    assert set(second.data_shard_of) == {0, 3}


# -------------------------------------------------- cluster integration


def _outputs_by_prompt(reqs):
    return {tuple(r.prompt): list(r.output) for r in reqs}


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-1.3b"])
def test_kill_one_engine_token_identity(arch, qwen, mamba):
    """The acceptance scenario: kill a busy engine mid-decode; its live
    sessions resume on the spare from their snapshots (KV pages for the
    attention arch, recurrent state leaves for mamba) and every output is
    token-identical to an unkilled reference run."""
    cfg, api, params = qwen if arch == "qwen2-1.5b" else mamba
    prompts = family_prompts(cfg.vocab, 6)

    def run(kill: bool):
        cluster = EngineCluster(api, params, n_engines=2, n_spares=1,
                                max_batch=2, max_seq=64, page_tokens=8,
                                heartbeat_timeout=3.0)
        reqs = [cluster.submit(p, max_new_tokens=12) for p in prompts]
        if kill:
            for _ in range(3):
                cluster.step()
            victim = max((e for e in range(2)),
                         key=lambda e: (len(cluster.engines[e].active),
                                        len(cluster.engines[e].waiting)))
            assert cluster.engines[victim].active, "kill landed on idle"
            cluster.kill(victim)
        done = cluster.run_until_done(max_steps=600)
        assert len(done) == len(reqs) and all(r.done for r in reqs)
        assert len({r.rid for r in done}) == len(done), "duplicated rids"
        return cluster, done

    ref_cluster, ref = run(kill=False)
    cluster, done = run(kill=True)
    assert cluster.sessions_migrated >= 1, "no session resumed from snapshot"
    assert cluster.policy.steals == 1 and cluster.monitor.deaths == 1
    assert _outputs_by_prompt(done) == _outputs_by_prompt(ref)


def test_strict_migration_republishes_in_target_volume(qwen):
    """Each engine is its own durability domain: a STRICT session that
    migrates off a dead engine re-logs its committed extent in the
    TARGET's oplog; the dead source's frozen log still replays the
    pre-kill extents (recovery could read them)."""
    cfg, api, params = qwen
    logs = []

    def make_oplog():
        device, log = fresh_oplog()
        logs.append(log)
        return log

    cluster = EngineCluster(api, params, n_engines=2, n_spares=1,
                            max_batch=2, max_seq=64, page_tokens=8,
                            heartbeat_timeout=3.0, mode=Mode.STRICT,
                            make_oplog=make_oplog, prefix_cache=False)
    prompts = family_prompts(cfg.vocab, 4, prefix_len=16, seed=3)
    reqs = [cluster.submit(p, max_new_tokens=16) for p in prompts]
    for _ in range(4):
        cluster.step()
    victim = max(range(2), key=lambda e: len(cluster.engines[e].active))
    assert cluster.engines[victim].active
    cluster.kill(victim)
    done = cluster.run_until_done(max_steps=600)
    assert len(done) == len(reqs) and cluster.sessions_migrated >= 1
    spare_eid = cluster._engine_of_shard[victim]
    assert spare_eid == 2
    # the dead volume froze mid-flight: its replay still holds extents
    assert replay_kv_commits(logs[victim].scan()), "frozen log lost extents"
    # the spare logged the restored extents + subsequent decode commits in
    # ITS volume; once its sessions finished they were tombstoned
    spare_entries = list(logs[spare_eid].scan())
    assert spare_entries, "migration published nothing in the target volume"
    assert replay_kv_commits(spare_entries) == {}, "finished seqs not unlinked"


def test_straggler_steal_detaches_live_source(qwen):
    """A LIVE straggler is stolen from: sessions detach (free_seq
    tombstones each sequence in the straggler's own volume, so its replay
    ends empty) and finish on the spare."""
    cfg, api, params = qwen
    logs = []

    def make_oplog():
        device, log = fresh_oplog()
        logs.append(log)
        return log

    cluster = EngineCluster(api, params, n_engines=2, n_spares=1,
                            max_batch=2, max_seq=64, page_tokens=8,
                            heartbeat_timeout=50.0, patience=2,
                            mode=Mode.STRICT, make_oplog=make_oplog,
                            prefix_cache=False)
    rng = np.random.default_rng(0)
    reqs = []
    for eid in range(2):                  # both engines busy -> real median
        for _ in range(2):
            req = cluster.engines[eid].submit(
                list(rng.integers(1, cfg.vocab, 12)), max_new_tokens=32,
                mode=Mode.STRICT)
            req.engine_id = eid
            reqs.append(req)
    for _ in range(2):
        cluster.step()
    victim = 1
    cluster.slow(victim, 1000.0)
    for _ in range(100):
        cluster.step()
        if cluster.policy.steals:
            break
    assert cluster.policy.steals == 1 and cluster.monitor.deaths == 0
    assert not cluster.engines[victim].active, "straggler kept sessions"
    assert cluster.sessions_migrated >= 1
    done = cluster.run_until_done(max_steps=600)
    assert len(done) == len(reqs) and all(r.done for r in reqs)
    # live-source detach: every migrated (and finished) sequence was
    # unlinked in the straggler's volume -> replay resurrects nothing
    assert replay_kv_commits(logs[victim].scan()) == {}


def test_remesh_rescues_onto_survivor_without_spares(qwen):
    cfg, api, params = qwen
    cluster = EngineCluster(api, params, n_engines=2, n_spares=0,
                            max_batch=4, max_seq=64, page_tokens=8,
                            heartbeat_timeout=3.0)
    prompts = family_prompts(cfg.vocab, 6, seed=11)
    reqs = [cluster.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(3):
        cluster.step()
    victim = max(range(2), key=lambda e: (len(cluster.engines[e].active),
                                          len(cluster.engines[e].waiting)))
    assert cluster.engines[victim].active or cluster.engines[victim].waiting
    cluster.kill(victim)
    done = cluster.run_until_done(max_steps=600)
    assert len(done) == len(reqs) and all(r.done for r in reqs)
    assert cluster.policy.remeshes == 1
    assert cluster.router.n_shards == 1
    survivor = 1 - victim
    # the shrunken ring routes every new session to the survivor
    post = cluster.submit(list(range(1, 9)), max_new_tokens=2)
    assert post.engine_id == survivor
    cluster.run_until_done(max_steps=100)
    assert post.done


def test_parked_restore_drains_and_cancel_while_parked(qwen):
    """A snapshot whose target has no free slot PARKS; it stays visible in
    ``waiting`` (the driver keeps pumping), retries each tick, and drains
    once the survivor frees a slot.  Cancelling a parked session resolves
    it without a restore."""
    cfg, api, params = qwen
    cluster = EngineCluster(api, params, n_engines=2, n_spares=0,
                            max_batch=2, max_seq=96, page_tokens=8,
                            heartbeat_timeout=2.0)
    rng = np.random.default_rng(1)

    def direct(eid, n_tokens):
        req = cluster.engines[eid].submit(
            list(rng.integers(1, cfg.vocab, 10)), max_new_tokens=n_tokens)
        req.engine_id = eid
        return req

    survivors = [direct(0, 64), direct(0, 64)]   # survivor full for a while
    victims = [direct(1, 24), direct(1, 24)]
    for _ in range(3):
        cluster.step()
    assert len(cluster.engines[1].active) == 2
    cluster.kill(1)
    for _ in range(60):
        cluster.step()
        if cluster.migrations:
            break
    assert cluster.migrations == 1
    st = cluster.stats()
    assert st["pending_restores"] == 2, "full survivor should park both"
    assert {r.rid for r in cluster.waiting} == {r.rid for r in victims}, \
        "parked sessions must stay driver-visible in waiting"
    cluster.cancel(victims[0])
    assert victims[0].done and victims[0].cancelled
    assert victims[0] in cluster.finished
    done = cluster.run_until_done(max_steps=800)
    assert len(done) == 4 and all(r.done for r in survivors + victims)
    assert cluster.sessions_migrated == 1      # the uncancelled victim
    assert cluster.restore_retries > 0         # it re-parked while full
    assert cluster.stats()["pending_restores"] == 0
    assert len(victims[1].output) == 24


def test_cluster_routing_affinity_end_to_end(qwen):
    cfg, api, params = qwen
    client = ServeClient(api, params, n_engines=2, max_batch=8, max_seq=64,
                         page_tokens=16)
    sess = client.open_session()
    prompts = family_prompts(cfg.vocab, 8, families=2, prefix_len=16,
                             seed=5)
    reqs = [sess.submit(p, max_new_tokens=2) for p in prompts]
    # same 16-token prefix => same home engine, submit after submit
    for fam in (0, 1):
        eids = {r.engine_id for r in reqs[fam::2]}
        assert len(eids) == 1, f"family {fam} scattered across {eids}"
    assert client.engine.router.spills == 0
    client.run_until_done()
    assert all(r.done for r in reqs)
    st = client.stats()
    assert st["cluster"]["router"]["routed_home"] == len(prompts)


def test_client_rejects_shared_oplog_in_cluster_mode(qwen):
    cfg, api, params = qwen
    _, log = fresh_oplog()
    with pytest.raises(ValueError):
        ServeClient(api, params, n_engines=2, oplog=log)


# --------------------------------------------------------------- tokenizer


def test_tokenizer_round_trips_exactly():
    tok = ByteTokenizer()
    for text in ["", "hello, world", "naïve café — ¿sí?", "日本語テスト",
                 "emoji 🙂🚀", "tabs\tand\nnewlines\x00nul"]:
        ids = tok.encode(text)
        assert all(1 <= i <= 256 for i in ids), "id 0 is the pad id"
        assert tok.decode(ids) == text


def test_tokenizer_degrades_untrusted_ids():
    tok = ByteTokenizer()
    # out-of-byte-range model tokens and torn multi-byte sequences both
    # degrade to U+FFFD instead of raising — generation is untrusted
    assert tok.decode([300]) == "�"
    ids = tok.encode("ab🙂")
    assert "�" in tok.decode(ids[:-2]) and \
        tok.decode(ids[:-2]).startswith("ab")
    mixed = tok.encode("ok") + [999] + tok.encode("go")
    assert tok.decode(mixed) == "ok�go"


def test_tokenizer_vocab_guard():
    with pytest.raises(ValueError):
        ByteTokenizer(vocab=256)
    assert ByteTokenizer(vocab=257).vocab_needed == 257


def test_session_text_prompt_equals_token_path(qwen):
    cfg, api, params = qwen
    assert cfg.vocab >= ByteTokenizer.vocab_needed
    text = "split the file system"
    client = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8)
    out_text = list(client.open_session().generate(text, max_new_tokens=6))
    ids = client.tokenizer.encode(text)
    solo = ServeClient(api, params, max_batch=2, max_seq=64, page_tokens=8)
    out_ids = list(solo.open_session().generate(ids, max_new_tokens=6))
    assert out_text == out_ids and len(out_text) == 6


# -------------------------------------------------- one engine per device

_PLACED_CLUSTER_SCRIPT = r"""
import json
import jax
from repro.configs import get_config
from repro.models import build_model
from repro.models.spec import init_params
from repro.serve import EngineCluster
import numpy as np

cfg = get_config("qwen2-1.5b", smoke=True)
api = build_model(cfg)
params = init_params(api.init_specs(), jax.random.PRNGKey(0))
rng = np.random.default_rng(7)
heads = [list(rng.integers(1, cfg.vocab, 16)) for _ in range(2)]
prompts = [heads[i % 2] + list(rng.integers(1, cfg.vocab, 6 + i % 5))
           for i in range(6)]

def placed(eng):
    return sorted({str(d) for x in jax.tree.leaves((eng.params, eng.caches))
                   for d in x.devices()})

def run(kill):
    cluster = EngineCluster(api, params, n_engines=2, n_spares=1,
                            max_batch=2, max_seq=64, page_tokens=8,
                            heartbeat_timeout=3.0)
    reqs = [cluster.submit(p, max_new_tokens=12) for p in prompts]
    if kill:
        for _ in range(3):
            cluster.step()
        cluster.kill(max(range(2), key=lambda e: len(cluster.engines[e].active)))
    cluster.run_until_done(max_steps=600)
    return cluster, [r.output for r in reqs]

_, clean = run(False)
cluster, faulted = run(True)
print("RESULT " + json.dumps({
    "devices": [str(e.device) for e in cluster.engines],
    "placed": [placed(e) for e in cluster.engines],
    "migrated": cluster.sessions_migrated,
    "same": clean == faulted}))
"""


def test_cluster_places_each_engine_on_its_own_device():
    """With several devices in the process, each engine holds its own copy
    of the weights and its own pools on ``jax.devices()[i]``, and a kill
    migrates sessions across devices with token-identical outputs.  Runs on
    three virtual CPU devices in a subprocess: the device count is fixed
    when jax starts."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=3").strip()
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", _PLACED_CLUSTER_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stderr[-4000:]}"
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert len(set(out["devices"])) == 3
    assert out["placed"] == [[d] for d in out["devices"]]
    assert out["migrated"] >= 1
    assert out["same"]


def test_single_device_cluster_keeps_default_placement(qwen):
    cfg, api, params = qwen
    cluster = EngineCluster(api, params, n_engines=2, n_spares=1,
                            max_batch=2, max_seq=64, page_tokens=8)
    assert len(jax.devices()) == 1
    assert all(e.device is None for e in cluster.engines)
    assert all(e.params is params for e in cluster.engines)

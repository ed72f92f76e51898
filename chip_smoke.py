#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: qwen2-1.5b at its published widths.

    python chip_smoke.py                # one chip: ServeClient -> Session ->
                                        # ServingEngine -> serve_step -> the
                                        # Pallas kv_append / paged attention
    python chip_smoke.py --four-chips   # only the cluster path: 3 shard
                                        # engines + 1 spare, one per chip

Weights and prompts are random, made from ``--seed``.  One process drives
every chip it uses and starts no other.  Every figure it prints is from a
smoke run, not a benchmark.  Its last line of output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
It exits non-zero without that line when JAX finds no TPU, when the
kernels would not be the Pallas ones, or when any phase fails.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import PMDevice  # noqa: E402
from repro.core.kvcache import replay_kv_commits  # noqa: E402
from repro.core.modes import Mode  # noqa: E402
from repro.core.oplog import OP_KV_COMMIT, OpLog  # noqa: E402
from repro.kernels.common import resolve_impl  # noqa: E402
from repro.launch.jax_setup import device_line, enable_compile_cache  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.spec import init_params  # noqa: E402
from repro.serve import ServeClient  # noqa: E402

ARCH = "qwen2-1.5b"
PAGE_TOKENS = 128
MAX_BATCH = 8
MAX_SEQ = 2048
NEW_TOKENS = 32
SHARED_PREFIX = 512

# Pallas vs ref logits, as a share of the largest |ref logit| in the rows
# compared.  Both paths compute in bf16 with f32 accumulation; they differ
# in the order of the softmax sums and where each rounds to bf16 (unit
# roundoff 2**-8).  Through 28 layers those roundings grow to a few ulps of
# the logit scale, which this bound allows; a wrong page, head or mask
# moves logits by the order of their own scale.
LOGIT_TOL = 2.0 ** -5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


# ------------------------------------------------------------------ device


def check_device(n_chips: int) -> jax.Device:
    """The TPU this run needs, and the Pallas kernels on it; raises
    otherwise."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU ({device_line()})")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips "
                         f"({device_line()})")
    impl = resolve_impl()
    if impl != "pallas":
        raise SystemExit(f"chip_smoke: kernels resolve to {impl!r}, not "
                         f"'pallas' (is REPRO_KERNEL_IMPL set?)")
    return dev


@contextlib.contextmanager
def kernel_impl(impl: str):
    """Trace under ``REPRO_KERNEL_IMPL=impl``."""
    old = os.environ.get("REPRO_KERNEL_IMPL")
    os.environ["REPRO_KERNEL_IMPL"] = impl
    try:
        yield
    finally:
        if old is None:
            del os.environ["REPRO_KERNEL_IMPL"]
        else:
            os.environ["REPRO_KERNEL_IMPL"] = old


# ------------------------------------------------------------------ model


def build(cfg, seed: int):
    api = build_model(cfg)
    params = jax.block_until_ready(
        init_params(api.init_specs(), jax.random.PRNGKey(seed)))
    return api, params


def compile_steps(api, params, caches, widths: Sequence[int],
                  impl: str) -> Tuple[Dict[int, object], Dict[int, float]]:
    """AOT-compile ``serve_step`` once per step width with the kernels
    ``impl`` selects.  A fresh jit per call: tracing reads the impl."""
    step = jax.jit(lambda p, t, c, n: api.serve_step(p, t, c, n))
    compiled, seconds = {}, {}
    for width in widths:
        tokens = jax.ShapeDtypeStruct((MAX_BATCH, width), np.int32)
        n_new = jax.ShapeDtypeStruct((MAX_BATCH,), np.int32)
        t0 = time.perf_counter()
        with kernel_impl(impl):
            compiled[width] = step.lower(params, tokens, caches,
                                         n_new).compile()
        seconds[width] = time.perf_counter() - t0
    return compiled, seconds


class StepProbe:
    """The engine's step: the compiled step of the run's width.  The first
    step of each width also runs the ``ref`` step on the same inputs and
    keeps both logits at the valid positions."""

    def __init__(self, steps: Dict[int, object],
                 ref_steps: Optional[Dict[int, object]] = None) -> None:
        self.steps = steps
        self.ref_steps = ref_steps
        self.pairs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}

    def __call__(self, params, tokens, caches, n_new):
        width = tokens.shape[1]
        logits, new_caches = self.steps[width](params, tokens, caches, n_new)
        if self.ref_steps is not None and width not in self.pairs:
            ref_logits, _ = self.ref_steps[width](params, tokens, caches,
                                                  n_new)
            n = np.asarray(n_new)
            rows = [(b, c) for b in range(len(n)) for c in range(n[b])]
            bi, ci = (np.array(x) for x in zip(*rows))
            self.pairs[width] = (
                np.asarray(logits, np.float32)[bi, ci],
                np.asarray(ref_logits, np.float32)[bi, ci])
        return logits, new_caches


def logit_error(got: np.ndarray, ref: np.ndarray) -> Tuple[float, float]:
    """(max |got - ref| / max |ref|, share of rows with the same argmax)."""
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    return err, agree


# ------------------------------------------------------------------ workload


def make_prompts(vocab: int, seed: int, n: int = 8) -> List[List[int]]:
    """``n`` prompts of 256-1024 tokens; the even ones share a 512-token
    prefix."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(1, vocab, SHARED_PREFIX).tolist()
    prompts = []
    for i in range(n):
        if i % 2 == 0:
            tail = int(rng.integers(128, 1024 - SHARED_PREFIX + 1))
            prompts.append(prefix + rng.integers(1, vocab, tail).tolist())
        else:
            length = int(rng.integers(256, 1025))
            prompts.append(rng.integers(1, vocab, length).tolist())
    return prompts


def new_oplog() -> OpLog:
    return OpLog(PMDevice(size=16 * 1024 * 1024), base_block=1,
                 num_blocks=64)


def serve(api, params, prompts: List[List[int]], step_fn) -> dict:
    """Serve ``prompts`` through one ServeClient with a POSIX and a STRICT
    session and the prefix cache on.  The first wave holds one sharer of
    the prefix and every unshared prompt; the other sharers come once it
    has finished, so they find the published prefix."""
    oplog = new_oplog()
    client = ServeClient(api, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                         page_tokens=PAGE_TOKENS, oplog=oplog,
                         prefix_cache=True, step_fn=step_fn)
    sessions = [client.open_session(Mode.POSIX),
                client.open_session(Mode.STRICT)]
    first = [0] + [i for i in range(len(prompts)) if i % 2]
    waves = [first, [i for i in range(len(prompts)) if i not in first]]
    reqs = {}
    t0 = time.perf_counter()
    for wave in waves:
        for i in wave:
            reqs[i] = sessions[(i // 2) % 2].submit(
                prompts[i], max_new_tokens=NEW_TOKENS)
        client.run_until_done(max_steps=2000)
    wall = time.perf_counter() - t0
    ordered = [reqs[i] for i in range(len(prompts))]
    out = {"reqs": ordered, "wall_s": wall, "steps": client.engine.steps,
           "prefix": client.stats()["prefix_cache"], "oplog": oplog}
    # sessions and client refer to each other: free the engine's pools
    # before the next run allocates its own
    del client, sessions
    gc.collect()
    return out


def check_served(run: dict, prompts: List[List[int]]) -> int:
    """Every request finished in full, a sharer hit the prefix cache, and
    the STRICT pages were published in the oplog; returns the publishes."""
    reqs = run["reqs"]
    for r, p in zip(reqs, prompts):
        if not r.done or r.stalled or r.truncated or r.cancelled:
            raise AssertionError(
                f"request {r.rid} did not finish in full: done={r.done} "
                f"stalled={r.stalled} truncated={r.truncated}")
        if len(r.output) != NEW_TOKENS or r.prompt != p:
            raise AssertionError(f"request {r.rid}: {len(r.output)} tokens")
    if run["prefix"]["hits"] < 1:
        raise AssertionError(f"no prefix-cache hit: {run['prefix']}")
    # only STRICT sequences log: one OP_KV_COMMIT per full page they
    # published (their own or adopted from the prefix cache), and an
    # OP_UNLINK when they finished
    entries = run["oplog"].scan()
    commits = sum(e.op == OP_KV_COMMIT for e in entries)
    full = sum((len(r.prompt) + len(r.output) - 1) // PAGE_TOKENS
               for r in reqs if r.mode is Mode.STRICT)
    if commits != full:
        raise AssertionError(f"oplog holds {commits} page publishes, the "
                             f"STRICT requests filled {full} pages")
    if replay_kv_commits(entries):
        raise AssertionError("finished sequences still replay as live")
    return commits


def token_agreement(a: List, b: List) -> Tuple[int, int]:
    same = sum(x == y for ra, rb in zip(a, b)
               for x, y in zip(ra.output, rb.output))
    return same, sum(len(r.output) for r in a)


# ------------------------------------------------------------------ phases


def one_chip(seed: int) -> None:
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    api, params = build(cfg, seed)
    log(f"{cfg.name}: {cfg.n_layers} layers d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab}, random "
        f"weights from seed {seed} in {time.perf_counter() - t0:.1f}s")
    caches = api.init_caches(MAX_BATCH, MAX_SEQ, PAGE_TOKENS)
    widths = (PAGE_TOKENS, 1)
    steps, secs = compile_steps(api, params, caches, widths, "pallas")
    ref_steps, ref_secs = compile_steps(api, params, caches, widths, "ref")
    del caches
    for width in widths:
        log(f"compile seconds, step width {width}: pallas "
            f"{secs[width]:.1f}, ref {ref_secs[width]:.1f}")
        if "tpu_custom_call" not in steps[width].as_text():
            raise AssertionError(f"width-{width} step holds no Pallas kernel")
    log("compiled steps hold tpu_custom_call (the Pallas kernels)")

    prompts = make_prompts(cfg.vocab, seed)
    probe = StepProbe(steps, ref_steps)
    run = serve(api, params, prompts, probe)
    publishes = check_served(run, prompts)
    n_prompt = sum(map(len, prompts))
    n_out = sum(len(r.output) for r in run["reqs"])
    log(f"smoke run, not a benchmark: {len(prompts)} requests, {n_prompt} "
        f"prompt + {n_out} generated tokens in {run['wall_s']:.2f}s wall, "
        f"{run['steps']} engine steps")
    pc = run["prefix"]
    log(f"prefix cache: hits={pc['hits']} tokens_saved={pc['tokens_saved']}"
        f"; STRICT pages published in the oplog: {publishes}")

    for width, name in ((PAGE_TOKENS, "first prefill chunk"),
                        (1, "first decode step")):
        got, ref = probe.pairs[width]
        err, agree = logit_error(got, ref)
        log(f"{name}: {len(got)} rows, max|pallas-ref|/max|ref| = {err:.3e}"
            f" (tol {LOGIT_TOL:.3e}), argmax agree {agree:.3f}")
        if not err <= LOGIT_TOL:
            raise AssertionError(f"{name}: logits off by {err:.3e}")

    ref_run = serve(api, params, prompts, StepProbe(ref_steps))
    check_served(ref_run, prompts)
    same, total = token_agreement(run["reqs"], ref_run["reqs"])
    log(f"greedy tokens equal to the ref path: {same}/{total} "
        f"({same / total:.3f})")
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak if peak is not None else 'not reported'}")


def placement(engine) -> set:
    """The devices holding an engine's weights and caches."""
    return {str(d) for x in jax.tree.leaves((engine.params, engine.caches))
            for d in x.devices()}


def step_until(client, cond, limit: int = 200) -> None:
    for _ in range(limit):
        if cond():
            return
        client.step()
    raise AssertionError(f"condition not met in {limit} cluster steps")


def four_chips(seed: int) -> None:
    """3 shard engines + 1 spare, each with its own weights and pools on its
    own chip.  The busiest engine is killed once every prompt is ingested;
    outputs must equal an unkilled run's."""
    cfg = get_config(ARCH)
    api, params = build(cfg, seed)
    prompts = make_prompts(cfg.vocab, seed, n=12)

    def run(kill: bool) -> dict:
        client = ServeClient(api, params, max_batch=MAX_BATCH,
                             max_seq=MAX_SEQ, page_tokens=PAGE_TOKENS,
                             prefix_cache=True, n_engines=3, n_spares=1,
                             make_oplog=new_oplog, heartbeat_timeout=4.0)
        cluster = client.engine
        sessions = [client.open_session(Mode.POSIX),
                    client.open_session(Mode.STRICT)]
        first = [0] + [i for i in range(len(prompts)) if i % 2]
        reqs = {i: sessions[(i // 2) % 2].submit(prompts[i], NEW_TOKENS)
                for i in first}
        step_until(client, lambda: not any(r.in_prefill
                                           for r in reqs.values()))
        for i in range(len(prompts)):
            if i not in reqs:
                reqs[i] = sessions[(i // 2) % 2].submit(prompts[i],
                                                        NEW_TOKENS)
        step_until(client, lambda: not any(
            r.in_prefill or r.slot is None for r in reqs.values()))
        victim = None
        if kill:
            victim = max(range(3), key=lambda e: (
                len(cluster.engines[e].active), -e))
            cluster.kill(victim)
        t0 = time.perf_counter()
        done = client.run_until_done(max_steps=4000)
        wall = time.perf_counter() - t0
        ordered = [reqs[i] for i in range(len(prompts))]
        out = {"reqs": ordered, "done": done, "victim": victim,
               "wall_s": wall, "stats": cluster.stats(),
               "devices": [str(e.device) for e in cluster.engines],
               "placed": [placement(e) for e in cluster.engines]}
        # the engines' weights and pools are freed only once the sessions,
        # which refer back to the client, are gone too
        del client, cluster, sessions
        gc.collect()
        return out

    clean = run(kill=False)
    faulted = run(kill=True)
    devices = faulted["devices"]
    log(f"engine devices: {devices}")
    if len(set(devices)) != 4:
        raise AssertionError(f"engines share devices: {devices}")
    if faulted["placed"] != [{d} for d in devices]:
        raise AssertionError(f"weights or pools off their engine's device: "
                             f"{faulted['placed']}")
    st = faulted["stats"]
    log(f"killed engine {faulted['victim']}: migrations={st['migrations']} "
        f"migrated={st['sessions_migrated']} "
        f"requeued={st['sessions_requeued']} fault={st['fault']}")
    if st["sessions_migrated"] < 1:
        raise AssertionError("no session resumed from its snapshot")
    for run_ in (clean, faulted):
        rids = [r.rid for r in run_["done"]]
        if len(rids) != len(set(rids)):
            raise AssertionError("a request finished twice")
        if sorted(rids) != sorted(r.rid for r in run_["reqs"]):
            raise AssertionError("a request was lost")
        for r in run_["reqs"]:
            if not r.done or r.truncated or len(r.output) != NEW_TOKENS:
                raise AssertionError(f"request {r.rid} did not finish")
    same = [a.output == b.output
            for a, b in zip(clean["reqs"], faulted["reqs"])]
    log(f"smoke run, not a benchmark: {len(prompts)} requests, none lost "
        f"or duplicated; decode after the kill {faulted['wall_s']:.2f}s "
        f"wall vs {clean['wall_s']:.2f}s unkilled")
    log(f"outputs equal to the unkilled run: {sum(same)}/{len(same)}")
    if not all(same):
        raise AssertionError("a migrated request's output changed")


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cluster path, one engine per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    dev = check_device(n_chips)
    log(f"{device_line()} (compile cache: {enable_compile_cache()})")
    if args.four_chips:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

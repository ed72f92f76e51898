"""One run of one cell: build the served model from the cell's files, warm
up, drive the window, compute the metrics, check correctness.

Everything that belongs to a configuration, a traffic mix, a cell's
limits or a per-layer metric is a file found by the name
``BENCHMARK.json`` gives it:

  configuration   the file its ``configs`` entry names
  traffic mix     bench/traffic/<traffic>.json
  limits          bench/limits/<cell>.json
  per-layer read  bench/metrics/<metric>.py, a ``read(run)`` function
  reference       bench/reference/<config's "reference">.py
  family          bench/families/<config's "reference">.py: the served
                  sizes, the FLOP and byte counts and the traced kernels

A configuration file states only keys that something accounts for: its
family reads them or names them as left to other code, the file lists
them under ``reduced`` or ``assumed``, or they are the file's own
bookkeeping (``BOOKKEEPING``).  Any other key stops the run before
anything is built, so no published size drops out of the served model.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / "chiprun_out" / "bench_trace"
OPLOG_BLOCKS = 1024          # 64 K page publishes: far more than a run makes
BOOKKEEPING = frozenset({"name", "source", "paper", "deployment", "serving",
                         "reference", "reduced", "assumed"})


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict
    config: Dict
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    peaks: Dict
    family: Any                  # the config's module under bench/families


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    config = load_json(root / cfg_entry["file"])
    return Cell(name=name, entry=entry, config=config,
                traffic=load_json(root / "bench" / "traffic"
                                  / f"{entry['traffic']}.json"),
                limits=load_json(root / "bench" / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer,
                peaks=load_json(root / "bench" / "peaks.json"),
                family=load_family(config, root))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(cfg: Dict, root: Path = ROOT):
    """The family module a configuration file's ``reference`` names.
    Raises SystemExit naming every key of the file that nothing accounts
    for."""
    path = root / "bench" / "families" / f"{cfg['reference']}.py"
    family = load_module(path)
    known = (BOOKKEEPING | set(family.READS) | set(family.LEAVES)
             | set(cfg.get("reduced", ())) | set(cfg.get("assumed", ())))
    stray = sorted(k for k in cfg if k not in known)
    if stray:
        raise SystemExit(f"bench: {cfg.get('name')}: {', '.join(stray)} "
                         f"neither read nor left by {path.name}, nor "
                         f"reduced or assumed in the file")
    return family


# ---------------------------------------------------------------- device


def device_peaks(cell: Cell, chips: int):
    """The chips this run needs; raises SystemExit without them."""
    import jax
    from repro.kernels.common import resolve_impl

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{dev.platform})")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    if dev.device_kind not in cell.peaks["devices"]:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{dev.device_kind!r} in bench/peaks.json")
    impl = resolve_impl()
    if impl != "pallas":
        raise SystemExit(f"bench: kernels resolve to {impl!r}, not pallas")
    return cell.peaks["devices"][dev.device_kind]


# ---------------------------------------------------------------- model


def program_config(cfg: Dict, family):
    """The program's ModelConfig for a configuration file: the repository's
    config of the same model, with every field the family reads from the
    file, in the type ``serving.param_dtype`` names."""
    import jax.numpy as jnp
    from repro.configs import get_config

    serving = cfg["serving"]
    return dataclasses.replace(
        get_config(serving["repo_config"]), **family.program_fields(cfg),
        param_dtype=getattr(jnp, serving["param_dtype"]))


def new_oplog():
    from repro.core import PMDevice
    from repro.core.oplog import OpLog
    from repro.core.pmem import BLOCK_SIZE

    return OpLog(PMDevice(size=(OPLOG_BLOCKS + 1) * BLOCK_SIZE),
                 base_block=1, num_blocks=OPLOG_BLOCKS)


def warm_up(engine, session) -> None:
    """Compile and run both step widths through the engine's own path: a
    short request takes one C-wide prefill step, then width-1 decode."""
    req = session.submit([1, 2, 3, 4, 5, 6, 7, 8], max_new_tokens=3)
    for _ in range(16):
        if req.done:
            break
        engine.step()
    if not req.done:
        raise RuntimeError("warm-up request did not finish")


# ---------------------------------------------------------------- run


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process0: float, peaks: Optional[Dict] = None,
        control: bool = False) -> Dict:
    """One run; the result line as a dict.  With ``control`` the float8
    reference takes the program's place in the comparison: its first
    token at each served position is scored in place of the served one,
    so ``correct`` comes out of the control's gap."""
    import jax
    from repro.core.modes import Mode
    from repro.launch.jax_setup import enable_compile_cache
    from repro.models import build_model
    from repro.models.spec import abstract_params
    from repro.serve import ServeClient

    from . import correctness, driver, e2e, tracing, traffic, weights

    log(f"compile cache: {enable_compile_cache()}")
    driver.CompileCounter.get()
    cfg, mix = cell.config, cell.traffic
    serving = cfg["serving"]
    pc = program_config(cfg, cell.family)
    api = build_model(pc)
    params = weights.make(abstract_params(api.init_specs()), seed,
                          pc.d_model, pc.tie_embeddings)
    B = int(serving["max_batch"])
    oplog = new_oplog()
    client = ServeClient(api, params, max_batch=B,
                         max_seq=int(serving["max_seq"]),
                         page_tokens=int(serving["page_tokens"]),
                         oplog=oplog,
                         prefix_cache=bool(serving["prefix_cache"]))
    engine = client.engine
    sessions = {False: client.open_session(Mode.POSIX),
                True: client.open_session(Mode.STRICT)}
    warm_up(engine, sessions[False])
    setup_s = time.perf_counter() - t_process0
    log(f"set-up {setup_s:.2f}s: {pc.name} {pc.n_layers} layers, B={B}")

    geom = engine.controller.geom
    cap = min(int(serving["max_seq"]) - 1,
              geom.max_tokens_per_seq - engine.chunk)
    gen = traffic.Generator(mix, seed, pc.vocab, cap)
    lead_in = float(mix["lead_in_s"])
    drain = float(mix.get("drain_s", 60.0))
    target = traffic.waiting_target(mix, B)

    trace_dir = TRACE_DIR / f"{cell.name}-{seed}"
    window_span = []

    def on_open():
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            span = jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN)
            span.__enter__()
            window_span.append(span)

    def on_close():
        if trace:
            window_span.pop().__exit__(None, None, None)
            jax.profiler.stop_trace()

    drv = driver.Driver(engine, sessions)
    if target is None:
        rec = drv.run(turns=gen.open_loop(lead_in + seconds + drain),
                      lead_in_s=lead_in, seconds=seconds, drain_s=drain,
                      on_open=on_open, on_close=on_close)
    else:
        rec = drv.run(backlog=gen.backlog(), waiting_target=target,
                      lead_in_s=lead_in, seconds=seconds, drain_s=drain,
                      on_open=on_open, on_close=on_close)
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")

    # what correctness needs of the engine, then free the served model
    ctrl = engine.controller
    T = int(serving["page_tokens"])
    finished = [r.req for r in rec.reqs if r.done and not r.truncated
                and r.req is not None]
    live_strict = {r.seq_id: ctrl.committed_extents(r.seq_id)
                   for r in list(engine.active.values())
                   if r.mode is Mode.STRICT}
    finished_pages = sum((len(r.req.prompt) + len(r.req.output) - 1) // T
                         for r in rec.reqs if r.done and r.req is not None
                         and r.req.mode is Mode.STRICT
                         and r.req.seq_id is not None)
    live_pages = sum(len(x) for x in live_strict.values())
    entries = oplog.scan()
    del drv, sessions, engine, client, ctrl
    gc.collect()

    rec.cell = {"family": cell.family, "config": cfg, "peaks": peaks or {}}
    if trace:
        rec.trace = tracing.summarize(tracing.find_xplane(str(trace_dir)),
                                      list(cell.family.KERNEL_COSTS))
        shutil.rmtree(trace_dir, ignore_errors=True)

    # metrics
    metrics: Dict[str, Dict] = {}
    if trace:
        for m in cell.per_layer:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = e2e.metric(m["name"], rec)
            if math.isnan(value):
                log(f"{m['name']}: nothing in the window to take it over")
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    attempted, n_failed = e2e.attempted_failed(rec)

    # correctness
    t_ref = time.perf_counter()
    ref_mod = load_module(BENCH / "reference" / f"{cfg['reference']}.py")
    reference = ref_mod.Reference(cfg, params)
    chosen = correctness.sample(finished, seed)
    gaps = correctness.logit_gaps(reference, chosen, control)
    mismatch, why = correctness.strict_log_mismatch(
        entries, live_strict, finished_pages, live_pages)
    ref_s = time.perf_counter() - t_ref
    limit_gap = float(cell.limits["max_logit_gap"])
    gap = gaps["control_logit_gap" if control else "max_logit_gap"]
    compared = {
        "max_logit_gap": {"value": gap, "limit": limit_gap},
        "strict_log_mismatch": {"value": mismatch, "limit": 0},
    }
    correct = bool(chosen) and gap <= limit_gap and mismatch == 0 \
        and not math.isnan(gap)

    result = {"correct": correct, "attempted": attempted,
              "failed": n_failed, "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem}}
    if trace:
        result["device"]["busy_s"] = rec.trace.busy_s
        result["device"]["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    steps = driver_summary(rec)
    log(f"window {rec.window[1] - rec.window[0]:.3f}s: {steps}")
    log(f"samples {e2e.samples(rec)}; generator late p95 "
        f"{_p(rec.late_s, 95):.4f}s max {max(rec.late_s, default=0):.4f}s; "
        f"waiting at open {rec.waiting_at_open}, at close "
        f"{rec.waiting_at_close}; compiles in window "
        f"{rec.compiles_in_window}")
    log(f"reference: {gaps['requests']} requests, {gaps['tokens']} tokens "
        f"({gaps['adopted']} adopted a prefix, {gaps['strict']} STRICT) in "
        f"{ref_s:.2f}s; served tokens' gap {gaps['max_logit_gap']:.6f}"
        + (f", float8 control's gap {gaps['control_logit_gap']:.6f} (the "
           f"one compared)" if control else ""))
    if why:
        log(f"STRICT log: {why}")
    due = e2e.due_in_window(rec)
    half = len(due) // 2
    ttft_halves = [_p([r.token_times[0] - r.due for r in part
                       if r.token_times], 50)
                   for part in (due[:half], due[half:])]
    ttft_all, itl_all = e2e.ttft_s(rec)[0], e2e.itl_s(rec)
    result["diagnostics"] = {
        "ttft_ms": {f"p{q}": _p(ttft_all, q) * 1e3 for q in (50, 75, 90)},
        "itl_ms": {f"p{q}": _p(itl_all, q) * 1e3
                   for q in (50, 90, 95, 97, 98, 99)},
        "ttft_p50_halves_s": ttft_halves,
        "due_unadmitted_at_close": sum(
            1 for r in due if r.admitted is None or r.admitted > rec.window[1]),
        "late_p95_s": _p(rec.late_s, 95),
        "waiting_open_close": [rec.waiting_at_open, rec.waiting_at_close],
        "compiles_in_window": rec.compiles_in_window,
        "steps": steps, "samples": e2e.samples(rec),
        "reference": {k: gaps[k] for k in ("requests", "tokens", "adopted",
                                           "strict")},
        "strict_publishes": correctness.publishes(entries),
        "reference_s": ref_s, "setup_s": setup_s}
    result["limits"] = compared
    for k, v in compared.items():
        log(f"compared {k} = {v['value']!r} (limit {v['limit']!r})")
    return result


def _p(xs, q) -> float:
    import numpy as np
    return float(np.percentile(xs, q)) if xs else 0.0


def driver_summary(rec) -> Dict:
    from . import layer_stats
    ws = layer_stats.window_steps(rec)
    pre = [s for s in ws if s.prefill]
    return {"steps": len(ws), "prefill_steps": len(pre),
            "prefill_ms": layer_stats.step_ms(rec, True),
            "decode_ms": layer_stats.step_ms(rec, False),
            "tokens_fed": sum(n for s in ws for n, _ in s.fed)}

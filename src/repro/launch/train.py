"""Training driver.

Smoke-scale runs execute for real on this host; production shapes go
through the dry-run (launch/dryrun.py).  The loop is the same fault-aware
code path a multi-host deployment runs (heartbeats, SplitFS checkpoints,
restore-on-restart), including the §9b escalation ladder: ``--spares N``
registers N idle spare workers with the ``FaultPolicy`` so a flagged
straggler's data shard is STOLEN (metadata-only reassignment, the spare
replays the shard deterministically) before any remesh is considered.
On the multi-host deployment every host runs this same driver with its own
``--worker`` id; spare hosts simply pass a worker id from the spare range
and idle inside ``run_training`` until a StealPlan names them.

  python -m repro.launch.train --arch qwen2-1.5b --smoke --steps 50
  python -m repro.launch.train --arch mamba2-1.3b --smoke --steps 100 \
      --ckpt-every 20 --mode strict --spares 2
"""

from __future__ import annotations

import argparse

import jax

from ..checkpoint import CheckpointManager
from ..configs import ARCH_IDS, get_config
from ..core import Mode, PMDevice, USplit, Volume, VolumeGeometry
from ..data import TokenPipeline
from ..dist.fault import FaultPolicy, HeartbeatMonitor
from ..models import build_model
from ..train import AdamWConfig, LoopConfig, run_training
from .jax_setup import device_line, enable_compile_cache
from .mesh import make_host_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced smoke config instead of its "
                         "published widths")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mode", choices=["posix", "sync", "strict"],
                    default="sync")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--worker", type=int, default=0,
                    help="this host's worker id (multi-host deployment)")
    ap.add_argument("--workers", type=int, default=1,
                    help="shard-owning workers in the deployment")
    ap.add_argument("--spares", type=int, default=0,
                    help="idle spare workers registered with the fault "
                         "policy (work-stealing pool, DESIGN.md §9b)")
    ap.add_argument("--heartbeat-timeout", type=float, default=0.0,
                    help="seconds of silence before a worker is declared "
                         "dead; 0 (default) disables death detection — "
                         "REQUIRED single-host, where only this process's "
                         "own heartbeats exist and every other registered "
                         "worker would spuriously 'die' after 60s. "
                         "Multi-host deployments pass a real timeout.")
    args = ap.parse_args()

    enable_compile_cache()
    print(f"[train] {device_line()}")
    cfg = get_config(args.arch, smoke=args.smoke)
    api = build_model(cfg)
    mesh = make_host_mesh()
    pipeline = TokenPipeline(cfg, global_batch=args.global_batch,
                             seq_len=args.seq_len, seed=args.seed)

    device = PMDevice(size=512 * 1024 * 1024)
    volume = Volume.format(device, VolumeGeometry(
        meta_blocks=512, journal_blocks=1024, oplog_slots=2, oplog_blocks=512))
    store = USplit(volume, mode=Mode[args.mode.upper()],
                   staging_file_bytes=16 * 1024 * 1024, staging_prealloc=4)
    ckpt = CheckpointManager(store)
    workers = list(range(args.workers))
    spares = list(range(args.workers, args.workers + args.spares))
    monitor = HeartbeatMonitor(
        workers + spares,
        timeout_s=args.heartbeat_timeout or float("inf"))
    policy = None
    if spares:
        # the spare-worker pool: stragglers get stolen from before the
        # remesh fallback is ever planned (steal-vs-remesh, DESIGN.md §9b)
        policy = FaultPolicy(
            monitor, assignment={w: w for w in workers}, spares=spares,
            chips_per_worker=max(len(jax.devices()) // max(args.workers, 1), 1),
            model_axis=mesh.shape.get("model", 1),
            pod_axis=mesh.shape.get("pod", 1))

    result = run_training(
        api, mesh, pipeline,
        LoopConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                   microbatches=args.microbatches, seed=args.seed),
        AdamWConfig(lr=args.lr, warmup_steps=max(2, args.steps // 10),
                    total_steps=args.steps),
        ckpt=ckpt, monitor=monitor, worker=args.worker, policy=policy)
    print(f"[train] {args.arch}: ran {result.steps_run} steps, "
          f"loss {result.losses[0]:.3f} -> {result.losses[-1]:.3f}"
          + (f" (restored from step {result.restored_from})"
             if result.restored_from else ""))
    if result.mitigations:
        print(f"[train] mitigations: {result.mitigations}")
    if result.remesh_pending is not None:
        print(f"[train] remesh pending: {result.remesh_pending.mesh_shape}")
    print(f"[train] store: {store.stats}")


if __name__ == "__main__":
    main()

"""Shared CLI wiring for the four parts.

Preserves the reference's per-node launch contract (README.md:8-19):

    python main.py --num-nodes N [--rank R --master-ip IP --master-port P]

with the same defaults (master 10.10.1.1:4000, rank inferred from a
``nodeN`` hostname — reference part2/part2a/main.py:20-39), the same batch
math (per-node ``int(256/num_nodes)``, part2/part2b/main.py:177), the same
seed (89395), loss-print cadence (every 20 iters) and the iteration-1..39
timing harness.

TPU-native extensions (no reference equivalent): one process automatically
drives all of its local chips as dp slots, and env knobs
(``TPU_DDP_MAX_ITERS``, ``TPU_DDP_GLOBAL_BATCH``, ``TPU_DDP_SYNTH_SIZE``)
shrink a run for smoke tests; ``TPU_DDP_COMPUTE_DTYPE`` overrides the
matmul dtype (f32 runs for drift measurement),
``TPU_DDP_STEPS_PER_DISPATCH`` groups K optimizer steps per dispatch,
``TPU_DDP_DISPATCH_DEPTH`` sizes the engine's async dispatch window
(0 = fully synchronous loop; docs/DESIGN.md §13),
``TPU_DDP_OVERLAP=1`` buckets the gradients (``TPU_DDP_BUCKET_MB`` MiB
per bucket) and issues each bucket's collective from inside the
backward pass with the sharded weight update on the all_reduce/fused
rungs (tpu_ddp/parallel/overlap.py; docs/DESIGN.md §18),
and ``TPU_DDP_SHARD_EVAL=1`` opts into the process-sharded dp-psum'd
evaluation (CIFAR path).
"""

from __future__ import annotations

import argparse
import sys


def parse_arguments(argv=None, require_num_nodes: bool = False):
    """The reference's flag surface (part2/part2a/main.py:20-32).

    ``--master-port`` stays a string: the reference keeps it one because it
    goes into an env var (SURVEY.md §1 L6); here it is concatenated into the
    coordinator address. ``--num-nodes`` has no default in the reference and
    omitting it crashes init (SURVEY.md §3.5) — we keep it required for the
    distributed parts and default it to 1 for part1.
    """
    p = argparse.ArgumentParser()
    p.add_argument("--master-ip", type=str, default="10.10.1.1",
                   help="rendezvous coordinator IP (rank 0's)")
    p.add_argument("--master-port", type=str, default="4000",
                   help="rendezvous coordinator port")
    p.add_argument("--num-nodes", type=int,
                   required=require_num_nodes,
                   default=None if require_num_nodes else 1,
                   help="world size (number of processes)")
    p.add_argument("--rank", type=int, default=None,
                   help="process rank; default inferred from hostname "
                        "nodeN (reference part2/part2a/main.py:35-39)")
    p.add_argument("--data-root", type=str, default=None,
                   help="dataset root: CIFAR-10 batches dir for the "
                        "default config, or ImageNet numpy-shard dir "
                        "({split}_images.npy/{split}_labels.npy) for "
                        "--config resnet50_imagenet (default: search "
                        "standard paths / IMAGENET_DIR, fall back to "
                        "synthetic)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--config", type=str, default="vgg11_cifar10",
                   help="named run preset: vgg11_cifar10 (the reference "
                        "ladder) or resnet50_imagenet (the BASELINE.json "
                        "stretch scale-up)")
    p.add_argument("--ckpt-dir", type=str, default=None,
                   help="checkpoint directory; saves after each epoch "
                        "(TPU-native extension, no reference equivalent)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --ckpt-dir")
    args = p.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        p.error("--resume requires --ckpt-dir")  # fail before rendezvous
    return args


def run_part(part: str, argv=None):
    """Wire L6..L1 for one part (the reference's ``main()``,
    part2/part2b/main.py:169-195) and run train + eval."""
    distributed = part != "part1"
    args = parse_arguments(argv, require_num_nodes=distributed)

    # Late imports keep `--help` fast and let env vars set by wrappers
    # (e.g. XLA_FLAGS for simulated devices) take effect first.
    import os

    import jax

    from tpu_ddp.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    from tpu_ddp.data.loader import create_data_loaders
    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.bootstrap import (
        get_rank_from_hostname, init_distributed_setup, shutdown,
        test_distributed_setup)
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.parallel.sync import PART_TO_STRATEGY
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    world_size = args.num_nodes or 1
    # Hostname rank inference only applies to distributed launches; a
    # single-process world is always rank 0 (the reference's part1 takes no
    # args and never infers a rank — part1/main.py:114-130).
    if world_size <= 1:
        rank = 0
    elif args.rank is not None:
        rank = args.rank
    else:
        rank = get_rank_from_hostname()

    # Elastic membership (resilience/elastic.py). A JOINING process
    # rendezvouses via the launcher's membership record — the original
    # coordinator world no longer exists — and restores its state from
    # the beacon the surviving rank 0 wrote.
    from tpu_ddp.resilience import elastic as _elastic
    join_epoch = _elastic.join_epoch_from_env()
    elastic_ctl = _elastic.ElasticController.from_env()
    beacon = None
    base_world = world_size
    if join_epoch is not None:
        membership = _elastic.join_world(elastic_ctl, join_epoch)
        rank = int(membership["assignments"][str(elastic_ctl.worker_id)])
        world_size = int(membership["world"])
        base_world = int(membership.get("base_world", world_size))
        beacon = _elastic.beacon_dir(elastic_ctl.directory,
                                     int(membership["epoch"]))
        from tpu_ddp.parallel.bootstrap import DistributedContext
        ctx = DistributedContext(
            rank=rank, world_size=world_size,
            num_devices=len(jax.devices()),
            local_devices=tuple(jax.local_devices()),
            coordinator=membership["coordinator"],
            backend=jax.devices()[0].platform)
        print(f"[{part}] joined elastic epoch {membership['epoch']} as "
              f"rank {rank}/{world_size}")
    else:
        ctx = init_distributed_setup(args.master_ip, args.master_port,
                                     rank, world_size)
        if distributed:
            test_distributed_setup(ctx)

    cfg = TrainConfig.preset(args.config, epochs=args.epochs)
    # Per-node batch follows the LAUNCH world (base_world): elastic
    # membership changes keep each survivor's per-node batch fixed, so
    # the global batch scales with the live world — the standard
    # elastic-DDP contract (a joiner computes from base_world too).
    batch_size = cfg.per_node_batch_size(base_world)

    # Replicas on the mesh = data-parallel slots. One process with D local
    # devices contributes D slots; N single-device processes contribute N.
    mesh = make_mesh() if distributed else None
    dp_size = mesh.shape["dp"] if mesh is not None else 1

    # Autotuning (tpu_ddp/tune/): resolve BEFORE get_model so tuned
    # model-level knobs (pallas_bn, compute_dtype) reach construction.
    # batch_size above is safe — global_batch_size is never searched.
    if cfg.autotune != "off":
        from tpu_ddp import tune
        cfg = tune.resolve(cfg, strategy=PART_TO_STRATEGY[part],
                           mesh=mesh)

    # TPU_DDP_SHARD_EVAL=1: process-sharded test set + dp-psum'd eval
    # (1/N per-device eval compute) instead of the reference's
    # every-node-evaluates-everything semantics. CIFAR path only — the
    # ImageNet loader keeps the replicated contract.
    from tpu_ddp.utils.config import _env_bool
    shard_eval = _env_bool("TPU_DDP_SHARD_EVAL", False)
    if cfg.dataset == "imagenet":
        from tpu_ddp.data.imagenet import create_imagenet_loaders
        train_loader, test_loader = create_imagenet_loaders(
            rank=rank, world_size=world_size, batch_size=batch_size,
            root=args.data_root, seed=cfg.seed,
            image_size=cfg.image_size, num_classes=cfg.num_classes)
        shard_eval = False
    else:
        train_loader, test_loader = create_data_loaders(
            rank=rank, world_size=world_size, batch_size=batch_size,
            root=args.data_root, seed=cfg.seed,
            shard_eval=shard_eval)
        shard_eval = shard_eval and world_size > 1

    import jax.numpy as jnp
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      use_pallas_bn=cfg.pallas_bn,
                      compute_dtype=jnp.dtype(cfg.compute_dtype),
                      remat=cfg.remat, act_dtype=cfg.act_dtype)
    from tpu_ddp.utils.metrics import from_env as metrics_from_env
    from tpu_ddp.utils.profiling import profile_dir_from_env, profile_trace

    trainer = Trainer(model, cfg, strategy=PART_TO_STRATEGY[part], mesh=mesh,
                      metrics=metrics_from_env(rank=rank))
    start_epoch = 0
    start_iter = 0
    if beacon is not None:
        # The joiner's initial state is the canonical host tree the
        # surviving rank 0 beaconed at the membership epoch — a live
        # handoff, not a checkpoint-interval-old restore.
        import json as _json
        state = trainer.restore_checkpoint(beacon)
        with open(os.path.join(beacon, "beacon_meta.json")) as f:
            meta = _json.load(f)
        start_epoch = int(meta["epoch"])
        start_iter = int(meta["next_iter"])
        print(f"[{part}] joined with beaconed state at step {state.step} "
              f"(epoch {start_epoch}, iter {start_iter})")
    elif args.resume:
        state = trainer.restore_checkpoint(args.ckpt_dir)
        # Derive where to pick up from the restored step: completed
        # epochs = step // iters-per-epoch, and a MID-epoch checkpoint
        # (ckpt_every_iters > 0) additionally places the run step %
        # iters-per-epoch batches into its epoch — those are skipped so
        # no batch is double-trained and step accounting stays exact.
        iters_per_epoch = len(train_loader)
        if cfg.max_iters is not None:
            iters_per_epoch = min(iters_per_epoch, cfg.max_iters)
        iters_per_epoch = max(iters_per_epoch, 1)
        start_epoch = state.step // iters_per_epoch
        start_iter = state.step % iters_per_epoch
        print(f"[{part}] resumed from {args.ckpt_dir} at step {state.step} "
              f"(epoch {start_epoch}, iter {start_iter})")
    else:
        state = trainer.init_state()

    overlap_note = ""
    if getattr(trainer, "_overlap_active", False):
        d = trainer._overlap.describe()
        overlap_note = (f" overlap={d['n_buckets']}x{cfg.bucket_mb}MiB"
                        f"{'+sharded-update' if d['sharded_update'] else ''}")
    print(f"[{part}] strategy={PART_TO_STRATEGY[part]} world_size={world_size} "
          f"rank={rank} dp_slots={dp_size} per-node batch={batch_size} "
          f"platform={jax.devices()[0].platform}{overlap_note}")

    epoch = start_epoch
    pending_iter = start_iter
    while epoch < cfg.epochs:
        # Per-epoch reshuffle hook (reference part2/part2b/main.py:189).
        train_loader.set_epoch(epoch)
        try:
            # Deep profiling (TPU_DDP_PROFILE_DIR): trace the first epoch.
            with profile_trace(
                    profile_dir_from_env() if epoch == 0 else None):
                state, stats = trainer.train_epoch(
                    state, train_loader, epoch=epoch,
                    ckpt_dir=args.ckpt_dir, start_iter=pending_iter)
        except _elastic.MembershipChange as chg:
            # A peer left (or is rejoining): reshard the LIVE state
            # onto the new world and resume this epoch where it
            # stopped — no checkpoint restore, no restart.
            res = _elastic.apply_membership(trainer, chg, elastic_ctl)
            if res is None:
                return 0  # this worker is not in the new world
            state = res.state
            rank, world_size = res.rank, res.world
            # Data shards follow the new world; per-node batch stays.
            if cfg.dataset == "imagenet":
                train_loader, test_loader = create_imagenet_loaders(
                    rank=rank, world_size=world_size,
                    batch_size=batch_size, root=args.data_root,
                    seed=cfg.seed, image_size=cfg.image_size,
                    num_classes=cfg.num_classes)
            else:
                train_loader, test_loader = create_data_loaders(
                    rank=rank, world_size=world_size,
                    batch_size=batch_size, root=args.data_root,
                    seed=cfg.seed, shard_eval=shard_eval)
            pending_iter = res.next_iter
            continue  # same epoch, from the first untrained batch
        pending_iter = 0
        # Epoch-end checkpoint — unless the in-loop cadence just wrote
        # this exact step (avoids a duplicate write and, under ZeRO, a
        # duplicate optimizer-state gather collective).
        if args.ckpt_dir and not (cfg.ckpt_every_iters and state.step
                                  % cfg.ckpt_every_iters == 0):
            path = trainer.save_checkpoint(args.ckpt_dir, state)
            if path:
                print(f"[{part}] checkpoint saved: {path}")
        trainer.evaluate(state, test_loader, sharded=shard_eval)
        print(f"[{part}] epoch {epoch}: avg iter "
              f"{stats['avg_iter_s']:.4f}s over {stats['timed_iters']} timed "
              f"iters; {stats['iters']} iters total")
        epoch += 1

    shutdown(ctx)
    return 0


def main_for(part: str):
    sys.exit(run_part(part))

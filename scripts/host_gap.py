"""Host-gap micro-harness: dispatch-depth sweep on the streaming loop.

Measures what the async dispatch pipeline (tpu_ddp/train/pipeline.py,
round 6) buys over the synchronous per-step loop: for each depth in
``--depths`` the SAME jitted VGG step runs the SAME host batches through
``Trainer.train_epoch``, and we record

- ``steps_per_sec``  — epoch wall time over iterations (best of
  ``--reps``; CI hosts are noisy),
- ``host_gap_ms``    — wall time the host spent inside forced
  ``block_until_ready`` calls, i.e. idle-waiting on the device,
- ``forced_syncs``   — how many times the loop had to block at all.

Depth 0 is the pre-round-6 loop (one forced sync per step: the host
pays the full device-completion round-trip every iteration). Deeper
windows amortize that to ≤1 forced sync per ``depth`` steps, so
``host_gap_ms`` should shrink monotonically with depth — THAT is the
committed claim. On a CPU host the steps/sec delta is small (host and
"device" share the cores, so there is little compute to hide behind);
on a TPU each avoided sync is a host round trip the device would
otherwise sit idle for. The throughput delta there is not measured.

Writes ``experiments/host_gap.json`` and prints a markdown table.

Usage: JAX_PLATFORMS=cpu python scripts/host_gap.py
       python scripts/host_gap.py --depths 0,1,2,4 --iters 12 --reps 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--depths", default="0,1,2,4",
                    help="comma-separated dispatch depths to sweep")
    ap.add_argument("--iters", type=int, default=12,
                    help="train iterations per epoch run")
    ap.add_argument("--reps", type=int, default=2,
                    help="epoch repetitions per depth (best kept)")
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--out", default=None,
                    help="output JSON path (default "
                         "experiments/host_gap.json)")
    args = ap.parse_args(argv)
    depths = [int(d) for d in args.depths.split(",") if d != ""]

    import jax
    import numpy as np

    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.train.pipeline import depth_sweep
    from tpu_ddp.utils.config import TrainConfig

    # One-device mesh, fused-DDP strategy: the bench.py configuration,
    # minus the sweep dimensions that don't matter here. float32 keeps
    # the CPU step numerically boring; depth must not change the math
    # (depth_sweep reuses one jitted step across all depths).
    mesh = make_mesh(jax.devices()[:1])
    model = get_model("VGG11", compute_dtype=np.float32)
    trainer = Trainer(model, TrainConfig(log_every=10**6),
                      strategy="fused", mesh=mesh)
    state = trainer.init_state(seed=0)

    rng = np.random.default_rng(0)
    host_batches = [
        (rng.standard_normal(
            (args.batch_size, 32, 32, 3)).astype(np.float32),
         rng.integers(0, 10, (args.batch_size,)).astype(np.int32))
        for _ in range(args.iters)
    ]

    # Warm-up epoch (compile + allocator steady state) before timing.
    state, _ = trainer.train_epoch(state, list(host_batches),
                                   log=lambda s: None)

    results, state = depth_sweep(trainer, state, host_batches, depths,
                                 reps=args.reps)

    record = {
        "platform": jax.default_backend(),
        "devices": 1,
        "model": "VGG11",
        "batch_size": args.batch_size,
        "iters": args.iters,
        "reps": args.reps,
        "depths": results,
    }
    out = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "experiments", "host_gap.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")

    print(f"wrote {out}\n")
    print("| depth | steps/sec | host_gap_ms | forced_syncs |")
    print("|------:|----------:|------------:|-------------:|")
    for d in depths:
        c = results[str(d)]
        print(f"| {d} | {c['steps_per_sec']} | {c['host_gap_ms']} "
              f"| {c['forced_syncs']} |")
    return record


if __name__ == "__main__":
    main()

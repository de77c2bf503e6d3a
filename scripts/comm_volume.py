"""Communication-volume ladder: collectives + bytes per step, per rung.

The platform-independent analogue of the reference's scaling analysis
(CS744__Assignment_2.pdf §2.2.2 ring-reduce cost / §3.1 figures 2-4,
round-3 verdict item 4): instead of wall-clock scaling curves — which a
one-chip, one-core host cannot produce in kind — extract what each DP
rung actually PUTS ON THE WIRE from its compiled HLO. This is a
measurable claim about the programs themselves: gather/scatter's root
asymmetry, all-reduce == reduce_scatter + all_gather byte identity for
ZeRO, FSDP's per-leaf gather/scatter pairs.

For every rung of the ladder (part1..part5) the jitted train step is
compiled for an 8-device virtual CPU mesh at the reference's global
batch, the HLO is scanned for collective ops (the scanner lives in
``tpu_ddp/utils/hlo_comm.py``; this script re-exports it), and each
op's payload size is recorded along with its ring-algorithm wire cost
per device.

Each syncing rung is additionally compiled with the bf16 and int8
gradient wire formats (``TrainConfig.grad_compress``,
tpu_ddp/parallel/compress.py) and the compressed-vs-fp32 bytes/step
ratio recorded — the dtype breakdown doubles as the HLO-level proof
that the collective really executes at the reduced dtype.

Writes ``experiments/comm_volume.json`` and prints a markdown table
(pasted into EXPERIMENTS.md §10).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python scripts/comm_volume.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# Re-exported for tests/test_comm_volume.py, which pins the parser's
# op/shape/byte accounting through THIS module's names.
from tpu_ddp.utils.hlo_comm import (  # noqa: E402
    COLLECTIVES as _COLLECTIVES,
    DTYPE_BYTES as _DTYPE_BYTES,
    collective_volume,
    shape_bytes as _shape_bytes,
)

__all__ = ["_COLLECTIVES", "_DTYPE_BYTES", "_shape_bytes",
           "collective_volume", "main"]

COMPRESSORS = ("bf16", "int8")


def _rung_hlo(strategy: str, n_devices: int,
              grad_compress: str = "none") -> tuple[str, int]:
    """Compile one ladder rung's train step; (hlo_text, param_bytes)."""
    import numpy as np

    import jax

    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig
    from tpu_ddp.utils.hlo_comm import train_step_hlo

    mesh = make_mesh(jax.devices()[:n_devices])
    cfg = TrainConfig(grad_compress=grad_compress)
    model = get_model(cfg.model, num_classes=cfg.num_classes)
    trainer = Trainer(model, cfg, strategy=strategy, mesh=mesh)
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(cfg.global_batch_size, cfg.image_size,
                                   cfg.image_size, 3)).astype(np.uint8)
    y = rng.integers(0, cfg.num_classes,
                     size=cfg.global_batch_size).astype(np.int32)
    xb, yb, wb = trainer.put_batch(x, y)
    hlo = train_step_hlo(trainer, state, xb, yb, wb)
    param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(state.params))
    return hlo, param_bytes


def main(n_devices: int = 8) -> dict:
    from tpu_ddp.parallel.sync import PART_TO_STRATEGY

    results = {}
    for part, strategy in sorted(PART_TO_STRATEGY.items()):
        hlo, param_bytes = _rung_hlo(strategy, n_devices)
        vol = collective_volume(hlo, n_devices)
        vol["strategy"] = strategy
        vol["param_bytes"] = param_bytes
        print(f"[comm_volume] {part} ({strategy}): "
              f"{vol['total_collectives']} collectives, "
              f"{vol['total_wire_bytes_per_device'] / 1e6:.2f} MB/device",
              file=sys.stderr)
        # Compressed wire formats: a rung that never syncs has nothing
        # to compress (part1's Trainer would warn and degrade to none).
        if strategy != "none":
            compressed = {}
            base = vol["total_wire_bytes_per_device"]
            for spec in COMPRESSORS:
                chlo, _ = _rung_hlo(strategy, n_devices,
                                    grad_compress=spec)
                cvol = collective_volume(chlo, n_devices)
                cvol["reduction_vs_fp32"] = (
                    base / cvol["total_wire_bytes_per_device"]
                    if cvol["total_wire_bytes_per_device"] else None)
                compressed[spec] = cvol
                print(f"[comm_volume]   + {spec}: "
                      f"{cvol['total_wire_bytes_per_device'] / 1e6:.2f} "
                      f"MB/device "
                      f"({cvol['reduction_vs_fp32']:.2f}x less)",
                      file=sys.stderr)
            vol["compressed"] = compressed
        results[part] = vol
    out = {"n_devices": n_devices, "model": "VGG11/CIFAR-10",
           "note": "collectives per optimizer step from compiled HLO; "
                   "wire bytes use the ring-algorithm cost model; "
                   "'compressed' rows re-compile the rung with "
                   "grad_compress=bf16/int8 wire formats",
           "rungs": results}
    os.makedirs(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments"), exist_ok=True)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments", "comm_volume.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[comm_volume] wrote {path}", file=sys.stderr)

    # Markdown table for EXPERIMENTS.md.
    print("| part | strategy | collectives | ops | wire MB/device | "
          "bf16 MB (x) | int8 MB (x) |")
    print("|---|---|---|---|---|---|---|")
    for part, vol in results.items():
        ops = ", ".join(f"{k} x{v['count']}" for k, v in vol["ops"].items())
        comp_cells = []
        for spec in COMPRESSORS:
            c = vol.get("compressed", {}).get(spec)
            if c is None:
                comp_cells.append("-")
            else:
                comp_cells.append(
                    f"{c['total_wire_bytes_per_device'] / 1e6:.2f} "
                    f"({c['reduction_vs_fp32']:.2f}x)")
        print(f"| {part} | {vol['strategy']} | "
              f"{vol['total_collectives']} | {ops or '-'} | "
              f"{vol['total_wire_bytes_per_device'] / 1e6:.2f} | "
              f"{comp_cells[0]} | {comp_cells[1]} |")
    return out


if __name__ == "__main__":
    # Force the virtual CPU mesh BEFORE any backend touch.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    main(int(os.environ.get("N_DEVICES", "8")))

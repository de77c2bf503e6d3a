"""Compression sweep: (rung x wire format) -> bytes/step, steps/sec,
final loss.

The tentpole's three claims in one artifact
(``experiments/compress_sweep.json``):

1. **bytes/step** — scanned out of each combination's compiled HLO
   (utils/hlo_comm.py), so the reduction column is a statement about
   the program on the wire, not the Python that built it. The fused
   rung must show ~2x for bf16 and ~3.9x for int8 (the two-phase
   scheme's 8/(2w) bound, compress.py module docstring).
2. **steps/sec** — wall-clock over the same jitted step. On the 1-core
   virtual CPU mesh the collectives are memcpys, so this column mostly
   prices the quantize/dequantize compute the wire saving buys; on real
   ICI the bytes column is the one that turns into time.
3. **final loss** — a convergence smoke (synthetic 10-class problem,
   an MLP big enough that int8's block padding is noise): int8 with
   error feedback must land within 2% of the fp32 baseline's final
   loss; the noef ablation shows the drift the residual removes.

The model is deliberately NOT VGG: the sweep trains 20 combinations to
convergence, which VGG on a 1-core host cannot do inside any budget —
scripts/comm_volume.py carries the VGG-scale wire table instead (same
scanner, same ratios).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python scripts/compress_sweep.py
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

RUNGS = ("gather_scatter", "all_reduce", "fused", "zero", "fsdp")
SPECS = ("none", "bf16", "int8", "int8-noef")

TRAIN_STEPS = 120
TIME_STEPS = 20
BATCH = 64
HIDDEN = 2048


@dataclasses.dataclass(frozen=True)
class SweepMLP:
    """48 -> HIDDEN -> 10 MLP (~120k params): one jit-friendly shape
    whose fused chunk (~15k elems at dp=8) makes the int8 quantizer's
    256-block padding < 2% — the wire ratios reflect the format, not
    the model's smallness."""

    hidden: int = HIDDEN

    def init(self, key):
        import jax
        import jax.numpy as jnp
        k1, k2 = jax.random.split(key)
        d = 48
        return {
            "w1": (jax.random.normal(k1, (d, self.hidden), jnp.float32)
                   * (2.0 / d) ** 0.5),
            "b1": jnp.zeros((self.hidden,), jnp.float32),
            "w2": (jax.random.normal(k2, (self.hidden, 10), jnp.float32)
                   * (1.0 / self.hidden) ** 0.5),
            "b2": jnp.zeros((10,), jnp.float32),
        }

    def apply(self, params, x):
        import jax.numpy as jnp
        h = x.reshape(x.shape[0], -1).astype(jnp.float32)
        h = jnp.maximum(h @ params["w1"] + params["b1"], 0)
        return h @ params["w2"] + params["b2"]


def _data(n_steps, batch, seed=0):
    """Synthetic 10-class batches, fixed across combos so final losses
    are comparable. Overlapping clusters + 10% label noise keep an
    irreducible cross-entropy floor — a separable problem lets the
    120k-param MLP drive every combo's loss to ~0 and the 2%-of-fp32
    criterion degenerates to 0/0."""
    import numpy as np
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(10, 48)).astype(np.float32) * 0.8
    xs, ys = [], []
    for _ in range(n_steps):
        y = rng.integers(0, 10, size=batch).astype(np.int32)
        x = centers[y] + rng.normal(size=(batch, 48)).astype(np.float32)
        flip = rng.random(batch) < 0.1
        y = np.where(flip, rng.integers(0, 10, size=batch), y) \
            .astype(np.int32)
        xs.append(x.reshape(batch, 4, 4, 3))
        ys.append(y)
    return xs, ys


def run_combo(strategy, spec, xs, ys, n_devices):
    import jax
    import numpy as np

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig
    from tpu_ddp.utils.timing import warm_then_median_s
    from tpu_ddp.utils.hlo_comm import (collective_dtype_bytes,
                                        collective_volume, train_step_hlo)

    mesh = make_mesh(jax.devices()[:n_devices])
    cfg = TrainConfig(grad_compress=spec, learning_rate=0.02)
    tr = Trainer(SweepMLP(), cfg, strategy=strategy, mesh=mesh)
    state = tr.init_state()
    xb, yb, wb = tr.put_batch(xs[0], ys[0])

    hlo = train_step_hlo(tr, state, xb, yb, wb)
    vol = collective_volume(hlo, n_devices)

    losses = []
    for x, y in zip(xs, ys):
        state, loss = tr.train_step(state, *tr.put_batch(x, y))
        losses.append(float(np.mean(np.asarray(loss))))

    # steps/sec on the staged batch (no host put in the timed loop);
    # shared warm+window helper (utils/timing.py, round-8 consolidation).
    def timed_step():
        nonlocal state
        state, loss = tr.train_step(state, xb, yb, wb)
        return loss

    dt, _ = warm_then_median_s(timed_step, iters=TIME_STEPS, windows=1)

    final = float(np.mean(losses[-10:]))
    return {
        "wire_bytes_per_step_per_device": vol[
            "total_wire_bytes_per_device"],
        "collective_dtype_bytes": collective_dtype_bytes(hlo),
        "steps_per_sec": round(1.0 / dt, 2),
        "final_loss": round(final, 5),
        "first_loss": round(losses[0], 5),
    }


def main(n_devices: int = 8) -> dict:
    xs, ys = _data(TRAIN_STEPS, BATCH)
    results = {}
    for strategy in RUNGS:
        per = {}
        for spec in SPECS:
            per[spec] = run_combo(strategy, spec, xs, ys, n_devices)
            base = per["none"]
            if spec != "none":
                w = per[spec]["wire_bytes_per_step_per_device"]
                per[spec]["bytes_reduction_vs_fp32"] = round(
                    base["wire_bytes_per_step_per_device"] / w, 3) \
                    if w else None
                per[spec]["final_loss_delta_vs_fp32"] = round(
                    per[spec]["final_loss"] - base["final_loss"], 5)
                per[spec]["final_loss_rel_delta"] = round(
                    abs(per[spec]["final_loss"] - base["final_loss"])
                    / max(base["final_loss"], 1e-9), 5)
            print(f"[compress_sweep] {strategy}/{spec}: "
                  f"{per[spec]['wire_bytes_per_step_per_device']/1e3:.1f}"
                  f" kB/step, {per[spec]['steps_per_sec']:.1f} steps/s, "
                  f"final loss {per[spec]['final_loss']:.4f}",
                  file=sys.stderr)
        results[strategy] = per
    out = {
        "n_devices": n_devices,
        "model": f"MLP 48-{HIDDEN}-10 (~120k params), synthetic "
                 "10-class, "
                 f"{TRAIN_STEPS} steps @ batch {BATCH}",
        "note": "wire bytes from the compiled-HLO scan "
                "(utils/hlo_comm.py, ring cost model); steps/sec on the "
                "1-core virtual CPU mesh prices quantization compute, "
                "not wire time; final_loss averages the last 10 steps",
        "rungs": results,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(os.path.join(root, "experiments"), exist_ok=True)
    path = os.path.join(root, "experiments", "compress_sweep.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[compress_sweep] wrote {path}", file=sys.stderr)

    print("| rung | spec | kB/step/dev | reduction | steps/s | "
          "final loss | delta vs fp32 |")
    print("|---|---|---|---|---|---|---|")
    for strategy, per in results.items():
        for spec, r in per.items():
            red = r.get("bytes_reduction_vs_fp32")
            delta = r.get("final_loss_delta_vs_fp32")
            print(f"| {strategy} | {spec} | "
                  f"{r['wire_bytes_per_step_per_device']/1e3:.1f} | "
                  f"{f'{red:.2f}x' if red else '-'} | "
                  f"{r['steps_per_sec']:.1f} | {r['final_loss']:.4f} | "
                  f"{f'{delta:+.4f}' if delta is not None else '-'} |")
    return out


if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    main(int(os.environ.get("N_DEVICES", "8")))

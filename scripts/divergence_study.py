"""part2a vs part2b divergence, measured per update.

Round-3 verdict item 3: the scaling table shows part2a (gather/scatter)
and part2b (all-reduce) at the same world size ending 16 chaotic
iterations at different losses (5.39 vs 8.29 at w=4), hand-waved as
"chaotic regime". This script replaces the hand-wave with numbers: both
strategies step IN LOCKSTEP on identical batches (dp=4 virtual mesh,
f32 compute), recording per-iteration

- |loss_a - loss_b|, and
- max over leaves of max |param_a - param_b| (ABSOLUTE; the VGG
  weights are O(1e-2)-scale, so ~4e-9 absolute is f32 reduction-order
  noise),

so the artifact shows (a) the per-update difference is at reduction-
order magnitude, and (b) how batch-stats-BN dynamics amplify it
iteration by iteration — the measured mechanism behind the scaling
table's end-of-run spread.

Writes ``experiments/divergence_part2.json``.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
           python scripts/divergence_study.py
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(iters: int = 40, dp: int = 4, batch: int = 32,
         dtype: str = "float32") -> dict:
    import numpy as np

    import jax

    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    mesh = make_mesh(jax.devices()[:dp])
    rng = np.random.default_rng(89395)

    def build(strategy):
        cfg = TrainConfig(compute_dtype=dtype)
        model = get_model(cfg.model, num_classes=cfg.num_classes,
                          compute_dtype=np.dtype(dtype))
        tr = Trainer(model, cfg, strategy=strategy, mesh=mesh)
        return tr, tr.init_state()

    tr_a, st_a = build("gather_scatter")   # part2a
    tr_b, st_b = build("all_reduce")       # part2b

    def param_delta(pa, pb):
        worst = 0.0
        for a, b in zip(jax.tree.leaves(jax.device_get(pa)),
                        jax.tree.leaves(jax.device_get(pb))):
            d = float(np.max(np.abs(np.asarray(a, np.float64)
                                    - np.asarray(b, np.float64))))
            worst = max(worst, d)
        return worst

    trace = []
    for it in range(iters):
        x = rng.normal(size=(batch, 32, 32, 3)).astype(np.float32)
        y = rng.integers(0, 10, size=batch).astype(np.int32)
        ba = tr_a.put_batch(x, y)
        bb = tr_b.put_batch(x, y)
        st_a, la = tr_a.train_step(st_a, *ba)
        st_b, lb = tr_b.train_step(st_b, *bb)
        la = float(np.mean(np.asarray(la)))
        lb = float(np.mean(np.asarray(lb)))
        rec = {"iter": it, "loss_a": round(la, 6), "loss_b": round(lb, 6),
               "loss_delta": round(abs(la - lb), 9)}
        if it % 5 == 0 or it == iters - 1:
            rec["max_param_delta"] = param_delta(st_a.params, st_b.params)
        trace.append(rec)
        print(f"[divergence] it {it}: |dloss|={rec['loss_delta']:.2e}"
              + (f" max|dparam|={rec.get('max_param_delta', 0):.2e}"
                 if "max_param_delta" in rec else ""), file=sys.stderr)

    first_nonzero = next((r["iter"] for r in trace
                          if r["loss_delta"] > 0), None)
    out = {
        "config": {"dp": dp, "batch": batch, "iters": iters,
                   "dtype": dtype, "model": "VGG11",
                   "strategies": ["gather_scatter (part2a)",
                                  "all_reduce (part2b)"]},
        "first_iter_with_loss_delta": first_nonzero,
        "final_loss_delta": trace[-1]["loss_delta"],
        "final_max_param_delta": trace[-1].get("max_param_delta"),
        "trace": trace,
    }
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    os.makedirs(os.path.join(repo, "experiments"), exist_ok=True)
    path = os.path.join(repo, "experiments", "divergence_part2.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[divergence] wrote {path}", file=sys.stderr)
    return out


if __name__ == "__main__":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=4").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    main()

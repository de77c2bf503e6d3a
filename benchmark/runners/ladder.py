"""Runner ``ladder``: one rung of the sync ladder through the path a
user takes, ``Trainer.train_epoch`` over ``create_data_loaders``, wired
as ``parts/common.run_part`` wires them (one process, every chip of the
cell a dp slot, the preset's defaults, no ``TPU_DDP_*`` variable).

Traffic parameters: ``part`` (the rung, by the name of its part),
``check_against`` (the rung whose hand-written sync it is compared with
in set-up), ``check_steps``, ``check_tol``.

``train_throughput`` counts whole epochs only: the images of the epochs
completed, over the seconds spent inside ``train_epoch``. Epochs run
until ``--seconds`` have passed, so the window overshoots by at most one
epoch. No evaluation runs inside the window; one pass over the test set
after it feeds ``correct``.
"""

from __future__ import annotations

import math
import re
import time

import numpy as np

_LOSS_LINE = re.compile(r"\[epoch (\d+), iter (\d+)\] loss: (\S+)")


class TimedLoader:
    """The train loader, with the time the loop waits in ``next()``."""

    def __init__(self, loader, bench):
        self.loader, self.bench = loader, bench
        self.wait_s = 0.0
        self.batches = 0

    def __len__(self):
        return len(self.loader)

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)

    def __iter__(self):
        it = iter(self.loader)
        while True:
            t0 = time.perf_counter()
            with self.bench.span("bench.data_next"):
                item = next(it, None)
            if item is None:
                return
            self.wait_s += time.perf_counter() - t0
            self.batches += 1
            yield item


def _max_abs_diff(a: dict, b: dict) -> float:
    import jax

    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def run(bench) -> dict:
    import jax.numpy as jnp

    from tpu_ddp.data.loader import create_data_loaders
    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.parallel.sync import PART_TO_STRATEGY
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    traffic, config = bench.traffic, bench.config
    seed = bench.seed % (2 ** 31 - 1)
    cfg = TrainConfig.preset(config["preset"], **config.get("overrides", {}))
    if cfg.global_batch_size != config["global_batch_size"]:
        raise ValueError("the preset's global batch is not the "
                         "configuration file's")
    mesh = make_mesh(bench.devices)
    train_loader, test_loader = create_data_loaders(
        rank=0, world_size=1, batch_size=cfg.per_node_batch_size(1),
        seed=seed, synthetic_size=config.get("synthetic_size"))
    images_per_epoch = len(train_loader.labels)
    bench.phase("data")

    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      use_pallas_bn=cfg.pallas_bn,
                      compute_dtype=jnp.dtype(cfg.compute_dtype),
                      remat=cfg.remat, act_dtype=cfg.act_dtype)
    lines: list = []
    trainer = Trainer(model, cfg, strategy=PART_TO_STRATEGY[traffic["part"]],
                      mesh=mesh)
    state = trainer.init_state(seed=seed)
    bench.phase("init")

    # Set-up check: the same few steps, from the same state and on the
    # loader's first batches, on this rung and on the rung it is checked
    # against leave the same parameters.
    train_loader.set_epoch(0)
    first = []
    for item in train_loader:
        first.append(item)
        if len(first) == traffic["check_steps"]:
            break
    ends = []
    for rung in (traffic["check_against"], traffic["part"]):
        t = trainer if rung == traffic["part"] else Trainer(
            model, cfg, strategy=PART_TO_STRATEGY[rung], mesh=mesh)
        s = t.init_state(seed=seed)
        step_losses = []
        for item in first:
            s, loss = t.train_step(s, *t.put_batch(*item))
            step_losses.append(float(np.mean(np.asarray(loss))))
        ends.append(t.params_to_host(s))
    loss_at_start = step_losses[0]      # this rung, the initial weights
    rung_diff = _max_abs_diff(*ends)
    del ends, s, t
    bench.phase("check")

    # Warm-up through the epoch loop itself, on both shapes the window
    # uses: a full batch and the epoch's short last one.
    warm = [first[0]]
    short = images_per_epoch % len(first[0][1])
    if short:
        warm.append(tuple(a[:short] for a in first[0]))
    state, _ = trainer.train_epoch(state, warm, log=lines.append)
    bench.phase("warm_up")

    loader = TimedLoader(train_loader, bench)
    lines.clear()
    epochs, train_s, steps, epoch_s, epoch_losses = 0, 0.0, 0, 0.0, []
    bench.open_window()
    while bench.elapsed() < bench.seconds:
        bench.tick(unit_s=epoch_s)      # trace the last whole epoch
        loader.set_epoch(epochs)
        t0 = time.perf_counter()
        with bench.span("bench.train_epoch"):
            state, stats = trainer.train_epoch(state, loader, epoch=epochs,
                                               log=lines.append)
        epoch_s = time.perf_counter() - t0
        train_s += epoch_s
        steps += stats["iters"]
        epoch_losses.append(stats["last_loss"])
        epochs += 1
    bench.close_window()

    evaluation = trainer.evaluate(state, test_loader, log=lines.append)
    logged = [m for m in map(_LOSS_LINE.match, lines) if m]
    losses = [float(m.group(3)) for m in logged]
    first_epoch = [float(m.group(3)) for m in logged if m.group(1) == "0"]
    skipped = trainer.guard.total_skipped if trainer.guard else 0
    checks = {
        "losses_finite": all(map(math.isfinite, losses + epoch_losses
                                 + [evaluation["test_loss"]])),
        # lower at the end of the first measured epoch (the mean of its
        # last logged window; without one, its last step) than on the
        # initial weights
        "loss_fell": (first_epoch[-1] if first_epoch else epoch_losses[0])
        < loss_at_start,
        "no_step_skipped": skipped == 0,
        "rung_agrees": rung_diff <= traffic["check_tol"],
    }
    return {
        "correct": all(checks.values()),
        "attempted": steps,
        "failed": skipped,
        "values": {"train_throughput":
                   epochs * images_per_epoch / train_s},
        "counters": {
            "steps": steps, "epochs": epochs, "train_s": train_s,
            "data_wait_s": loader.wait_s, "data_batches": loader.batches,
            "items_per_s": epochs * images_per_epoch / train_s,
        },
        "notes": {"checks": checks, "rung_max_abs_diff": rung_diff,
                  "loss_at_start": loss_at_start,
                  "epoch_last_losses": epoch_losses, "window_losses": losses,
                  "test": evaluation},
    }

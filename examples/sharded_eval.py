"""Process-sharded evaluation demo — multi-process `evaluate(sharded=True)`.

The reference evaluates the full test set redundantly on every node
(reference part2/part2b/main.py:89-93). This CLI demonstrates the
TPU-native alternative for multi-process clusters: the test set is
sharded BY PROCESS in the loader (`create_data_loaders(shard_eval=True)`
— wrap-padding rows carry weight 0 so each example counts once
globally), each process's shard assembles into the global batch, and
the per-shard sums psum over dp. It runs BOTH evals and prints both
lines, so callers (tests/test_multiprocess.py) can assert the sharded
metrics equal the replicated ones.

Honours the reference launch contract, so the launcher can spawn it::

    python -m tpu_ddp.launch examples/sharded_eval.py --nproc 2

Env knobs: TPU_DDP_SYNTH_SIZE, TPU_DDP_GLOBAL_BATCH.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "parts"))

from common import parse_arguments  # noqa: E402


def main(argv=None) -> int:
    args = parse_arguments(argv, require_num_nodes=True)

    from tpu_ddp.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from tpu_ddp.data.loader import create_data_loaders
    from tpu_ddp.models import get_model
    from tpu_ddp.parallel.bootstrap import (get_rank_from_hostname,
                                            init_distributed_setup,
                                            shutdown,
                                            test_distributed_setup)
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig

    world = args.num_nodes or 1
    rank = (0 if world <= 1
            else args.rank if args.rank is not None
            else get_rank_from_hostname())
    ctx = init_distributed_setup(args.master_ip, args.master_port, rank,
                                 world)
    if world > 1:
        test_distributed_setup(ctx)

    # ViT, not VGG: batch-statistics BatchNorm (the VGG family's
    # reference-faithful semantic) computes its statistics over the
    # SHARD under sharded eval, so only per-example models (LayerNorm)
    # give bit-identical replicated-vs-sharded metrics to assert on.
    cfg = TrainConfig.preset("vit_cifar10")
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      compute_dtype=np.float32)
    mesh = make_mesh()
    trainer = Trainer(model, cfg, strategy="fused", mesh=mesh)
    state = trainer.init_state()
    print(f"[sharded_eval] rank={rank} world={world} "
          f"dp={mesh.shape['dp']}")

    batch = cfg.per_node_batch_size(world)
    # Replicated loader (the reference default) AND the process-sharded
    # one; same underlying (deterministic synthetic) test set.
    _, test_repl = create_data_loaders(rank=rank, world_size=world,
                                       batch_size=batch)
    _, test_shard = create_data_loaders(rank=rank, world_size=world,
                                        batch_size=batch,
                                        shard_eval=True)

    repl = trainer.evaluate(
        state, test_repl,
        log=lambda s: print(f"[replicated] {s}", flush=True))
    shard = trainer.evaluate(
        state, test_shard, sharded=True,
        log=lambda s: print(f"[sharded] {s}", flush=True))

    # The invariant the test asserts: identical global counts. The loss
    # is the reference's AVERAGE OF PER-BATCH MEANS (part1/main.py:108),
    # so a ragged final batch is weighted differently when the batch
    # boundaries differ (replicated: N-per-batch; sharded: N*world) —
    # only when every batch is full do the two averages coincide, and
    # then they must agree to reduction-order tolerance.
    assert shard["seen"] == repl["seen"], (shard, repl)
    assert shard["correct"] == repl["correct"], (shard, repl)
    if repl["seen"] % (batch * world) == 0:
        assert abs(shard["test_loss"] - repl["test_loss"]) < 1e-4, (
            shard, repl)
    else:
        assert abs(shard["test_loss"] - repl["test_loss"]) < 5e-2, (
            shard, repl)
    print(f"[sharded_eval] agreement ok: seen={shard['seen']} "
          f"correct={shard['correct']}", flush=True)

    shutdown(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())

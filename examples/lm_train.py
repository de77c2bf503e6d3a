"""Distributed transformer-LM training demo — the LM-engine analogue of
the parts/ CLIs.

Honours the reference launch contract (reference README.md:8-19), so the
local cluster launcher can spawn it::

    python -m tpu_ddp.launch examples/lm_train.py --nproc 2

or run it per node like any part::

    python examples/lm_train.py --num-nodes N --rank R \
        --master-ip IP --master-port P

Each process contributes its local devices as dp slots; batches are
synthetic tokens (zero egress), per-process shards assembled into global
arrays by the trainer. Env knobs: TPU_DDP_LM_STEPS, TPU_DDP_LM_PRESET,
TPU_DDP_LM_FSDP=1, TPU_DDP_GLOBAL_BATCH, TPU_DDP_LM_ACCUM (gradient-
accumulation microbatches), TPU_DDP_LM_SP_MODE (ring|ulysses),
TPU_DDP_LM_OPT (adamw|adafactor), TPU_DDP_LM_ZERO1=1 (ZeRO-1 optimizer
state sharding — Adafactor uses the row-sharded FactoredZeRO1; with
TPU_DDP_LM_TP>1 the elementwise wrapper lays tp-sharded leaves' state
out P((mp, dp))), TPU_DDP_LM_TP (Megatron tensor-parallel extent).
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

    from tpu_ddp.models import make_transformer
    from tpu_ddp.parallel.bootstrap import (get_rank_from_hostname,
                                            init_distributed_setup,
                                            shutdown,
                                            test_distributed_setup)
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import (LMTrainer, PipelineLMTrainer,
                                  make_lm_batch)

    world = args.num_nodes or 1
    rank = (0 if world <= 1
            else args.rank if args.rank is not None
            else get_rank_from_hostname())
    ctx = init_distributed_setup(args.master_ip, args.master_port, rank,
                                 world)
    if world > 1:
        test_distributed_setup(ctx)

    steps = int(os.environ.get("TPU_DDP_LM_STEPS", "5"))
    preset = os.environ.get("TPU_DDP_LM_PRESET", "TransformerLM-tiny")
    fsdp = os.environ.get("TPU_DDP_LM_FSDP", "0") == "1"
    accum = int(os.environ.get("TPU_DDP_LM_ACCUM", "1"))
    sp_mode = os.environ.get("TPU_DDP_LM_SP_MODE", "ring")
    # TPU_DDP_LM_OPT_SHARD: replicated | zero1 | zero2 (zero2 =
    # dp-scattered grad accumulation; pair with TPU_DDP_LM_ACCUM).
    # TPU_DDP_LM_ZERO1=1 is the legacy spelling of zero1.
    opt_shard = os.environ.get(
        "TPU_DDP_LM_OPT_SHARD",
        "zero1" if os.environ.get("TPU_DDP_LM_ZERO1", "0") == "1"
        else "replicated")
    # TPU_DDP_LM_CLIP: global-norm gradient clip threshold (0 = off).
    clip = float(os.environ.get("TPU_DDP_LM_CLIP", "0")) or None
    opt_name = os.environ.get("TPU_DDP_LM_OPT", "adamw")
    tp = int(os.environ.get("TPU_DDP_LM_TP", "1"))
    if tp < 1:
        raise ValueError(f"TPU_DDP_LM_TP={tp}: must be >= 1")
    # TPU_DDP_LM_PP>1 selects the pipeline rung; the schedule knobs
    # (TPU_DDP_PP_SCHEDULE / TPU_DDP_PP_MICROBATCHES /
    # TPU_DDP_PP_VIRTUAL) ride in through TrainConfig's env parsing so
    # the launch flags (--pp-schedule etc.) reach this CLI unchanged.
    pp = int(os.environ.get("TPU_DDP_LM_PP", "1"))
    if pp < 1:
        raise ValueError(f"TPU_DDP_LM_PP={pp}: must be >= 1")
    from tpu_ddp.utils.config import TrainConfig
    knobs = TrainConfig()
    pp_schedule = knobs.pp_schedule
    pp_micro = knobs.pp_microbatches or None   # 0 = auto (= pp)
    pp_virtual = knobs.pp_virtual
    global_batch = int(os.environ.get("TPU_DDP_GLOBAL_BATCH", "8"))
    # The batch axis shards over dp PROCESS GROUPS (world // tp), not
    # over every process: tp-group members feed the same rows.
    dp_groups = max(world // tp, 1)
    if global_batch % dp_groups:
        raise ValueError(f"TPU_DDP_GLOBAL_BATCH={global_batch} not "
                         f"divisible by dp process groups {dp_groups} "
                         f"(world {world} / tp {tp})")
    seq_len = 32

    model = make_transformer(preset, max_seq_len=seq_len,
                             compute_dtype=np.float32)
    mesh = make_mesh(mp=tp)
    if opt_name == "adafactor":
        from tpu_ddp.ops.optim import Adafactor
        optimizer = Adafactor(min_dim_size_to_factor=8)
    elif opt_name == "adamw":
        optimizer = None  # LMTrainer's AdamW default
    else:
        raise ValueError(f"TPU_DDP_LM_OPT={opt_name!r}: expected "
                         "'adamw' or 'adafactor'")
    if pp > 1:
        mesh = make_mesh(mp=tp, pp=pp)
        trainer = PipelineLMTrainer(
            model, mesh,
            num_micro=pp_micro,
            schedule=pp_schedule,
            pp_virtual=pp_virtual,
            param_sharding="fsdp" if fsdp else "replicated",
            opt_sharding=opt_shard,
            optimizer=optimizer,
            sp_mode=sp_mode, clip_grad_norm=clip)
    else:
        trainer = LMTrainer(
            model, mesh,
            param_sharding="fsdp" if fsdp else "replicated",
            opt_sharding=opt_shard,
            optimizer=optimizer,
            grad_accum=accum, sp_mode=sp_mode, clip_grad_norm=clip)
    state = trainer.init_state(seed=0)
    print(f"[lm_train] rank={rank} world={world} dp={trainer.dp} "
          f"sp={trainer.sp} tp={trainer.tp} pp={pp} fsdp={fsdp} "
          f"opt_shard={opt_shard} opt={opt_name} accum={accum} "
          f"clip={clip} preset={preset}"
          + (f" schedule={pp_schedule} micro={trainer.num_micro} "
             f"virtual={pp_virtual}" if pp > 1 else ""))

    # Deterministic synthetic tokens, identical on every process; each
    # process feeds ITS contiguous shard of the global batch.
    rng = np.random.default_rng(1234)
    tokens = rng.integers(0, model.vocab_size,
                          size=(global_batch, seq_len + 1))
    # Each PROCESS feeds its shard of the batch axis; with tp the
    # batch only shards over dp = world/tp process groups, so processes
    # in the same tp group feed the SAME rows (put_batch assembles by
    # process index; dp-major mesh order makes rank // tp the dp slot).
    # tp == 1 reduces to the plain per-rank split (slot == rank).
    per = global_batch // dp_groups
    slot = rank // tp
    local = tokens[slot * per:(slot + 1) * per]
    x, y = trainer.put_batch(*make_lm_batch(local))
    for step in range(steps):
        state, loss = trainer.train_step(state, x, y)
        # THIS process's shard losses (the global array is not fully
        # addressable across processes) — every node prints its own
        # running loss, as in the reference.
        mean = float(np.mean([np.asarray(s.data)
                              for s in loss.addressable_shards]))
        print(f"[lm_train] step {step + 1}/{steps} loss {mean:.4f}")
    shutdown(ctx)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""tpu_ddp — a TPU-native data-parallel training framework (JAX/XLA/pjit).

Built from scratch with the capabilities of the reference
(ruc98/Distributed-Data-Parallel-ML-Training): a four-part ladder of
gradient-synchronization strategies behind one training loop,

  part1  : single-device jit-compiled train step            (no sync)
  part2a : root-centric gather -> mean -> scatter            (manual sync)
  part2b : per-parameter all-reduce(SUM) / world_size        (manual sync)
  part3  : fused DP step — grads pmean'd inside one jitted
           step so XLA overlaps the ICI collective with the
           remaining backward pass                           (framework sync)

plus the surrounding framework: model zoo, host data pipeline with
DistributedSampler-parity sharding, distributed bootstrap over
``jax.distributed``, benchmark/timing harness, and a test suite.

The compute path is JAX/XLA (NHWC convs on the MXU, bf16-friendly); the
sync strategies are XLA collectives (`psum`, `all_gather`) over the device
mesh instead of the reference's gloo/TCP process group.
"""

__version__ = "0.1.0"

from tpu_ddp.utils.config import TrainConfig, SEED  # noqa: F401

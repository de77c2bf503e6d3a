"""Test configuration: force a virtual 8-device CPU platform.

The reference was verified on a real 4-node cluster and has no test suite
(SURVEY.md §4); our strategy is the one §4/§7 prescribe: multi-device tests
on the forced host platform.

``JAX_PLATFORMS`` is forced to ``cpu`` here, before jax is imported, so
``pytest`` on a machine that has a chip never takes the chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

from tpu_ddp.utils.compile_cache import enable_compile_cache  # noqa: E402

# The package's own cache rule (utils/compile_cache.py). Fresh trainer
# closures never hit the in-process jit cache, but the persistent cache
# keys on the HLO itself, so repeated step programs load instead of
# compiling. Checked on jaxlib 0.9.0 with the forced 8-device CPU
# platform: loading cached sharded-trainer executables works (the heap
# corruption older jaxlibs showed on cache LOADS is gone), a cold run
# costs the same as no cache, a warm run under half (tier-1 serial:
# 644 s cold, 264 s warm, 8 cores). The thresholds drop to zero because
# test programs compile in well under the default one second. Every
# load prints a `cpu_aot_loader` machine-feature line on stderr; on the
# machine that compiled the entry it is noise.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


_TRAINER_CACHE: dict = {}


def cached_vgg_trainer(devices, strategy, dp=4):
    """Session-cached VGG Trainer per (strategy, dp) — construction
    re-traces and reloads the compiled step from the persistent cache
    (~1-2 s each on the 1-core CI host). Trainers hold no per-run
    mutable state, so test modules share them and rebuild their own
    TrainStates. Per-process, so safe under `pytest -n auto`."""
    key = (strategy, dp)
    if key not in _TRAINER_CACHE:
        import numpy as np

        from tpu_ddp.models import get_model
        from tpu_ddp.parallel.mesh import make_mesh
        from tpu_ddp.train.engine import Trainer
        from tpu_ddp.utils.config import TrainConfig

        mesh = make_mesh(devices[:dp])
        model = get_model("VGG11", compute_dtype=np.float32)
        _TRAINER_CACHE[key] = Trainer(model, TrainConfig(),
                                      strategy=strategy, mesh=mesh)
    return _TRAINER_CACHE[key]


@pytest.fixture
def no_retrace():
    """The retrace sentinel (tpu_ddp/analysis/retrace.py) as a fixture:

        def test_loop(no_retrace):
            # a program's name: tpu_ddp/utils/profiling.py PROGRAMS
            with no_retrace(watch=(profiling.DDP_TRAIN_STEP,)):
                for _ in range(5):
                    trainer.train_step(state, *batch)

    Raises RetraceError on exit if any watched callable compiled more
    than once (the round-8 bug class: a "compiled" loop re-lowering
    every call)."""
    from tpu_ddp.analysis.retrace import no_retrace as _nr
    return _nr

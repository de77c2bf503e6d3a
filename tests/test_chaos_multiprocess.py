"""Chaos drills across REAL process boundaries.

Each test injects one fault kind from tpu_ddp/resilience/chaos.py into a
2-process localhost cluster (the test_multiprocess.py topology: separate
OS processes, jax.distributed rendezvous, cross-process collectives) and
asserts the matching recovery mechanism engages:

- ``nan-grad`` → the step guard skips the update on BOTH ranks (the
  poisoned gradient crosses the all-reduce), replicas stay bitwise
  identical, and training completes.
- ``stalled-step`` → the launcher's heartbeat watchdog kills the hung
  cluster well before the overall timeout and ``launch_elastic``
  restarts it to completion.
- ``corrupt-ckpt`` + ``hard-exit`` → the restarted run quarantines the
  damaged newest checkpoint and resumes from the previous verified one.
- ``host-loss`` / ``hard-exit`` under ``elastic_reshard`` → the
  SURVIVOR reshards its live TrainState onto the shrunken world (no
  restart, no checkpoint restore), for both the announced and the
  unannounced death.
- ``host-join`` under ``elastic_reshard`` → the departed worker rejoins
  a regrown epoch and restores from the survivors' state beacon.
"""

from __future__ import annotations

import json
import os
import time

import pytest

from tpu_ddp.launch import launch, launch_elastic

pytestmark = [pytest.mark.slow, pytest.mark.chaos]

SMOKE_ENV = {
    "TPU_DDP_SYNTH_SIZE": "64",
    "TPU_DDP_MAX_ITERS": "3",
    "TPU_DDP_GLOBAL_BATCH": "16",
    "CIFAR10_DIR": "/nonexistent-so-synthetic",
}


def _skipped_steps(metrics_path):
    events = [json.loads(l)
              for l in open(metrics_path).read().splitlines()]
    return [e["step"] for e in events if e["event"] == "step_skipped"]


def test_nan_grad_skipped_on_all_ranks(tmp_path):
    """Satellite (d): a NaN gradient injected on ONE rank at step 2 is
    skipped on BOTH (the poison crosses the all-reduce, the guard flag
    is psum-agreed), the per-step replica check stays clean, and the
    epoch completes with identical eval on both ranks."""
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_CHAOS_FAULTS": "nan-grad@2:rank=1",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_CHECK_REPLICAS_EVERY": "1",  # divergence would raise
        "TPU_DDP_METRICS_FILE": str(tmp_path / "metrics_{rank}.jsonl"),
    })
    res = launch("part3", nproc=2, env=env, echo=False, timeout=600)
    assert res.ok, "\n".join(w.output for w in res.workers)
    # BOTH ranks skipped exactly step 2 — a rank-local skip would have
    # tripped the replica check and failed the run.
    for rank in (0, 1):
        skipped = _skipped_steps(str(tmp_path / f"metrics_{rank}.jsonl"))
        assert skipped == [2], (rank, skipped)
        assert "Test set: average loss" in res.output_of(rank)
    # Synchronized params -> identical eval lines (invariant (ii)).
    line0 = [l for l in res.output_of(0).splitlines() if "Test set" in l]
    line1 = [l for l in res.output_of(1).splitlines() if "Test set" in l]
    assert line0 == line1
    # The injection actually happened where configured.
    assert "injecting nan-grad at step 2" in res.output_of(1)


def test_watchdog_recovers_hung_cluster(tmp_path, capfd):
    """A rank wedged mid-step (stalled-step chaos: one rank sleeps for
    an hour, the other blocks in the next collective) is detected by the
    heartbeat watchdog in ~heartbeat_timeout seconds — NOT the 600 s
    overall timeout — and the elastic restart completes the run from the
    mid-epoch checkpoint."""
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_CHAOS_FAULTS": "stalled-step@2",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_CKPT_EVERY": "1",
    })
    t0 = time.monotonic()
    res = launch_elastic(
        "part3", nproc=2, max_restarts=1, min_restart_interval=0.0,
        echo=False, timeout=600, heartbeat_timeout=20.0,
        extra_args=["--ckpt-dir", str(tmp_path / "ckpt")], env=env)
    elapsed = time.monotonic() - t0
    assert res.ok, "\n".join(w.output for w in res.workers)
    assert res.restarts == 1
    # Two attempts, each bounded by compile + a few steps + the 20 s
    # stall deadline: far below one attempt's 600 s timeout.
    assert elapsed < 500, elapsed
    out = capfd.readouterr().out
    assert "heartbeat stall" in out
    assert "resumed from" in res.output_of(0)


def test_corrupt_checkpoint_falls_back_on_restart(tmp_path):
    """Combined drill: at step 2 the writer corrupts the newest
    checkpoint, then hard-exits. The restarted run must quarantine the
    corpse and resume from the previous verified checkpoint (step 1),
    not die on the truncated npz."""
    ckpt_dir = tmp_path / "ckpt"
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_CHAOS_FAULTS": "corrupt-ckpt@2,hard-exit@2",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_CKPT_EVERY": "1",
    })
    res = launch_elastic(
        "part3", nproc=2, max_restarts=1, min_restart_interval=0.0,
        echo=False, timeout=600,
        extra_args=["--ckpt-dir", str(ckpt_dir)], env=env)
    assert res.ok, "\n".join(w.output for w in res.workers)
    assert res.restarts == 1
    out0 = res.output_of(0)
    # Resumed from step 1 — step 2's checkpoint was the corrupt one.
    assert "resumed from" in out0 and "at step 1" in out0, out0
    assert "Test set: average loss" in out0
    # The corpse was quarantined for post-mortem, never deleted.
    quarantined = [d for d in os.listdir(ckpt_dir) if ".corrupt" in d]
    assert any(d.startswith("step_00000002") for d in quarantined), \
        sorted(os.listdir(ckpt_dir))


def test_elastic_reshard_survives_host_loss(tmp_path):
    """The tentpole drill: rank 1 is gracefully preempted at step 2
    under elastic_reshard. The SURVIVOR must pull its live TrainState
    to host, rebuild the one-process world, reshard, and finish the
    run — zero restarts, zero checkpoint restores."""
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_CHAOS_FAULTS": "host-loss@2:rank=1",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_ELASTIC_RESHARD": "1",
    })
    res = launch("part3", nproc=2, env=env, echo=False, timeout=600,
                 elastic_reshard=True)
    assert res.ok, "\n".join(w.output for w in res.workers)
    assert res.reshards == 1
    # The departed rank's exit was absorbed, not counted as a failure.
    assert [(w.rank, w.absorbed) for w in res.workers
            if w.returncode != 0] == [(1, True)]
    out0 = res.output_of(0)
    assert "resharded in" in out0
    assert "resumed from" not in out0        # live carry, no checkpoint
    assert "Test set: average loss" in out0  # training went on to eval


def test_elastic_reshard_absorbs_unannounced_crash(tmp_path):
    """The UNANNOUNCED death: hard-exit leaves no departure note, so
    the survivor first hits the failed gloo collective, then must wait
    for the launcher to publish the shrunken epoch and convert the
    wreckage into a membership change (engine._raise_membership_change)
    instead of dying on the JaxRuntimeError."""
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_CHAOS_FAULTS": "hard-exit@2:rank=1",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_ELASTIC_RESHARD": "1",
    })
    res = launch("part3", nproc=2, env=env, echo=False, timeout=600,
                 elastic_reshard=True)
    assert res.ok, "\n".join(w.output for w in res.workers)
    assert res.reshards == 1
    out0 = res.output_of(0)
    assert "resharded in" in out0
    assert "resumed from" not in out0
    assert "Test set: average loss" in out0


def test_elastic_rejoin_restores_from_beacon(tmp_path):
    """host-join: the worker leaves at step 2 and rejoins — a shrink
    epoch then a regrow epoch, with the joiner restoring the LIVE state
    from the survivors' beacon instead of a checkpoint."""
    env = dict(SMOKE_ENV)
    env.update({
        "TPU_DDP_MAX_ITERS": "8",  # survivor must outlive the rejoin
        "TPU_DDP_CHAOS_FAULTS": "host-join@2:rank=1",
        "TPU_DDP_CHAOS_SENTINEL": str(tmp_path / "sentinels"),
        "TPU_DDP_ELASTIC_RESHARD": "1",
    })
    res = launch("part3", nproc=2, env=env, echo=False, timeout=600,
                 elastic_reshard=True)
    assert res.ok, "\n".join(w.output for w in res.workers)
    assert res.reshards == 2
    assert res.output_of(0).count("resharded in") >= 2
    assert any("joined with beaconed state" in w.output
               for w in res.workers)
    assert all("resumed from" not in w.output for w in res.workers)

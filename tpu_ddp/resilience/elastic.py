"""Elastic membership: live resharding instead of restart-from-checkpoint.

The restart ladder (launch_elastic + watchdog) treats any lost rank as
"kill the cluster, replay from the newest verified checkpoint" — a full
restart window per preemption. This module is the live alternative: on
a membership change the *surviving* processes keep their in-memory
TrainState, tear down the dead world's coordination layer, re-rendezvous
as a smaller (or regrown) world, rebind the Trainer against the new
mesh (engine.rebind_mesh) and re-place the state per its ShardingPlan
(parallel/redistribute.py). Recovery cost is one re-rendezvous plus one
retrace — seconds, not a restart window.

Why the bootstrap here is manual
--------------------------------
``jax.distributed`` assumes a static world: the XLA coordination
client's default missed-heartbeat/error callback LOG(FATAL)s the whole
process the moment the coordination service reports ANY task in error —
a dead peer kills the survivors (client.h:80, verified on this jaxlib).
Its ``shutdown()`` is no better: it runs a shutdown *barrier* over all
tasks, which a dead peer fails, which is again fatal. So elastic mode
builds the service/client itself with (a) a benign error callback,
(b) ``shutdown_on_destruction=False``, and (c) a sky-high
missed-heartbeat budget (liveness is the launcher's per-rank file
heartbeat watchdog, not the coordination service), and *leaks* the old
client/service objects on teardown instead of ever entering the
barrier. The leak is bounded: one small RPC stub per membership epoch.

The membership protocol (files under TPU_DDP_ELASTIC_DIR)
---------------------------------------------------------
- ``membership.json`` — the launcher's authoritative epoch record:
  ``{"epoch": N, "world": k, "assignments": {worker_id: new_rank},
  "coordinator": "ip:port", "joiners": [...], "dropped": [...]}``.
  Written atomically; workers poll its mtime once per step. The
  launcher assigns SURVIVORS the low ranks (in worker-id order) and
  joiners the highest — so the coordination service host (rank 0) and
  the beacon writer are always an already-running survivor, never the
  still-booting joiner.
- ``departures/<worker_id>`` — a departure *notice*. Written by a
  gracefully-preempted rank (chaos host-loss/host-join) before it
  exits, and by the launcher when it detects an abrupt exit. The
  notice is what closes the race: survivors stop dispatching doomed
  collectives at the next step boundary instead of discovering the
  death inside one.
- ``acks/epoch<N>.rank<worker_id>`` — written by each survivor after
  it has rebound at epoch N; the launcher waits for a full ack set
  before trusting the reshard (timeout -> restart fallback).
- ``beacon_epoch<N>/`` — a canonical-host-form state handoff written
  by the new rank 0 when the epoch admits joiners, read by the joining
  process as its initial state (a disk-mediated stand-in for the
  state-transfer RPC a multi-machine deployment would use; on one
  host it IS memory-to-memory through the page cache).

What still forces a restart is documented in docs/DESIGN.md §17 —
chiefly: state sharded across processes (ZeRO/FSDP at
process_count > 1) dies with its host, and a survivor that loses the
race and crashes inside a collective has donated its last good state
buffers to the failed step.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any

ELASTIC_ENV = "TPU_DDP_ELASTIC_RESHARD"
ELASTIC_DIR_ENV = "TPU_DDP_ELASTIC_DIR"
ELASTIC_RANK_ENV = "TPU_DDP_ELASTIC_RANK"
ELASTIC_JOIN_ENV = "TPU_DDP_ELASTIC_JOIN"

MEMBERSHIP_FILE = "membership.json"
DEPARTURES_DIR = "departures"
ACKS_DIR = "acks"

#: a survivor that cannot carry its live state (sharded across a dead
#: peer, or caught mid-collective) exits with this -> launcher falls
#: back to restart-from-checkpoint.
RESHARD_FALLBACK_EXIT = 17
#: a rank leaving with intent to return (chaos host-join drill).
HOST_JOIN_EXIT = 16
#: a rank preempted for good (chaos host-loss drill).
HOST_LOSS_EXIT = 15

_LEAKED: list = []  # keeps abandoned coordination stubs alive forever


def elastic_env_active() -> bool:
    return (os.environ.get(ELASTIC_ENV, "") not in ("", "0", "false")
            and bool(os.environ.get(ELASTIC_DIR_ENV)))


def join_epoch_from_env() -> int | None:
    v = os.environ.get(ELASTIC_JOIN_ENV)
    return int(v) if v else None


def membership_path(directory: str) -> str:
    return os.path.join(directory, MEMBERSHIP_FILE)


def write_membership(directory: str, membership: dict) -> None:
    """Atomic write — a worker's poll never sees a torn file."""
    os.makedirs(directory, exist_ok=True)
    path = membership_path(directory)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(membership, f, indent=2, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def read_membership(directory: str) -> dict | None:
    try:
        with open(membership_path(directory)) as f:
            return json.load(f)
    except (FileNotFoundError, json.JSONDecodeError):
        return None


def announce_departure(directory: str, worker_id: int,
                       reason: str = "lost") -> None:
    """The graceful-preemption notice. Dying ranks (and the launcher,
    on their behalf when death was abrupt) write it so survivors stop
    dispatching collectives at the NEXT step boundary rather than
    inside a doomed one."""
    dep = os.path.join(directory, DEPARTURES_DIR)
    os.makedirs(dep, exist_ok=True)
    tmp = os.path.join(dep, f".{worker_id}.tmp")
    with open(tmp, "w") as f:
        f.write(reason)
    os.replace(tmp, os.path.join(dep, str(worker_id)))


def clear_departure(directory: str, worker_id: int) -> None:
    """Launcher-side: forget a departure before the worker rejoins, so
    its NEXT departure re-triggers the survivors' fast path."""
    try:
        os.remove(os.path.join(directory, DEPARTURES_DIR, str(worker_id)))
    except OSError:
        pass


def reset_control_dir(directory: str) -> None:
    """Launcher-side scrub before (re)spawning a cluster: a stale
    departure note or high-epoch membership left by a previous attempt
    in a pinned directory would trigger a phantom reshard at step 0."""
    import shutil
    try:
        os.remove(membership_path(directory))
    except OSError:
        pass
    for sub in (DEPARTURES_DIR, ACKS_DIR):
        shutil.rmtree(os.path.join(directory, sub), ignore_errors=True)
    try:
        names = os.listdir(directory)
    except OSError:
        names = []
    for name in names:
        if name.startswith("beacon_epoch"):
            shutil.rmtree(os.path.join(directory, name),
                          ignore_errors=True)


def departures(directory: str) -> dict[int, str]:
    dep = os.path.join(directory, DEPARTURES_DIR)
    out: dict[int, str] = {}
    try:
        names = os.listdir(dep)
    except FileNotFoundError:
        return out
    for name in names:
        if name.startswith("."):
            continue
        try:
            with open(os.path.join(dep, name)) as f:
                out[int(name)] = f.read().strip()
        except (ValueError, OSError):
            continue
    return out


def ack_path(directory: str, epoch: int, worker_id: int) -> str:
    return os.path.join(directory, ACKS_DIR, f"epoch{epoch}.rank{worker_id}")


def write_ack(directory: str, epoch: int, worker_id: int) -> None:
    os.makedirs(os.path.join(directory, ACKS_DIR), exist_ok=True)
    with open(ack_path(directory, epoch, worker_id), "w") as f:
        f.write(str(time.time()))


def beacon_dir(directory: str, epoch: int) -> str:
    return os.path.join(directory, f"beacon_epoch{epoch}")


# ---------------------------------------------------------------------------
# Non-fatal coordination bootstrap.
# ---------------------------------------------------------------------------


# Liveness is the launcher's per-rank file-heartbeat watchdog, not the
# coordination service, so its own heartbeat budget is out of reach
# (the 10 s x 100000 beats the older interval/count pair gave).
_HEARTBEAT_TIMEOUT_S = 1_000_000


def bootstrap(coordinator: str, num_processes: int, process_id: int,
              init_timeout: int = 60) -> None:
    """Join (or re-join) a coordination world without the static-world
    fatalities of ``jax.distributed.initialize``. Safe to call after
    :func:`teardown_world`; rank 0 hosts the service."""
    from jax._src import distributed as jdist
    from jax._src.lib import _jax

    state = jdist.global_state
    if state.client is not None:
        raise RuntimeError("coordination already initialized; call "
                           "teardown_world() before re-bootstrapping")
    if process_id == 0:
        bind = "[::]:" + coordinator.rsplit(":", 1)[1]
        state.service = _jax.get_distributed_runtime_service(
            bind, num_processes,
            heartbeat_timeout=_HEARTBEAT_TIMEOUT_S)
    state.client = _jax.get_distributed_runtime_client(
        coordinator, process_id, init_timeout=init_timeout,
        heartbeat_timeout=_HEARTBEAT_TIMEOUT_S,
        missed_heartbeat_callback=_benign_coordination_error,
        shutdown_on_destruction=False, use_compression=True)
    state.client.connect()
    state.process_id = process_id
    state.num_processes = num_processes
    state.coordinator_address = coordinator


def _benign_coordination_error(status) -> None:
    # The default callback LOG(FATAL)s the process; peer liveness is the
    # launcher watchdog's job, so a coordination-layer error is only
    # telemetry here.
    print(f"[elastic] coordination-layer error (non-fatal): {status}",
          flush=True)


def teardown_world() -> None:
    """Abandon the current coordination world and the device backends.

    Never enters the XLA shutdown barrier (fatal with a dead peer, and
    it hangs under ``shutdown_on_destruction=False``): the old client
    and service objects are parked in a module-level leak list so their
    destructors never run, then every cached topology surface is
    dropped so the next backend construction sees the new world."""
    import jax
    from jax._src import distributed as jdist
    from jax._src import xla_bridge

    state = jdist.global_state
    _LEAKED.append((state.client, state.service,
                    state.preemption_sync_manager))
    state.client = None
    state.service = None
    state.preemption_sync_manager = None
    xla_bridge._clear_backends()
    jax.clear_caches()
    # lru-cached topology views survive _clear_backends; stale values
    # here mean meshes built for the DEAD world.
    for fn in (jax.process_count, jax.local_devices,
               xla_bridge.get_backend, xla_bridge.local_devices,
               xla_bridge.process_count):
        cache_clear = getattr(fn, "cache_clear", None)
        if cache_clear is not None:
            cache_clear()


# ---------------------------------------------------------------------------
# Worker-side controller.
# ---------------------------------------------------------------------------


class MembershipChange(Exception):
    """Raised out of the train loop at a step boundary; carries the live
    (device) TrainState and where the epoch should resume."""

    def __init__(self, membership: dict | None, state: Any, epoch: int,
                 next_iter: int):
        super().__init__(
            f"membership change at epoch={epoch} iter={next_iter}")
        self.membership = membership
        self.state = state
        self.epoch = epoch
        self.next_iter = next_iter


@dataclasses.dataclass
class Resumption:
    """What :func:`apply_membership` hands back to the run loop."""
    state: Any
    rank: int
    world: int
    epoch: int
    next_iter: int


class ElasticController:
    """Per-worker membership watch: one ``os.stat`` + one small
    ``listdir`` per train step, nothing else on the hot path."""

    def __init__(self, directory: str, worker_id: int,
                 epoch: int = 0):
        self.directory = directory
        self.worker_id = worker_id
        self.epoch = epoch          # last epoch this worker acked
        self._known_departed: set[int] = set()

    @classmethod
    def from_env(cls) -> "ElasticController | None":
        if not elastic_env_active():
            return None
        directory = os.environ[ELASTIC_DIR_ENV]
        worker_id = int(os.environ.get(ELASTIC_RANK_ENV, "0"))
        epoch = 0
        m = read_membership(directory)
        ctl = cls(directory, worker_id, epoch=epoch)
        if m is not None:
            ctl.epoch = int(m.get("epoch", 0))
            # Departures already folded into the current epoch are not
            # news — without this a controller built AFTER a reshard
            # (train_epoch makes a fresh one per epoch) would re-trip
            # on the absorbed worker's stale departure note.
            ctl._known_departed.update(
                int(w) for w in m.get("dropped", []))
        return ctl

    def changed(self) -> bool:
        """True when the world no longer matches the acked epoch: a
        newer membership record, or a departure notice from a member
        of the current world."""
        m = read_membership(self.directory)
        if m is not None and int(m.get("epoch", 0)) > self.epoch:
            return True
        for wid in departures(self.directory):
            if wid != self.worker_id and wid not in self._known_departed:
                return True
        return False

    def read(self) -> dict | None:
        return read_membership(self.directory)

    def await_membership(self, deadline_s: float = 60.0) -> dict:
        """Block until the launcher publishes an epoch newer than the
        one this worker last acked (the departure notice usually lands
        first). Timeout means the launcher is gone or stuck — the
        worker exits into the restart fallback."""
        t0 = time.monotonic()
        while True:
            m = read_membership(self.directory)
            if m is not None and int(m.get("epoch", 0)) > self.epoch:
                return m
            if time.monotonic() - t0 > deadline_s:
                raise TimeoutError(
                    f"no membership epoch > {self.epoch} within "
                    f"{deadline_s:.0f}s")
            time.sleep(0.05)


def apply_membership(trainer, chg: MembershipChange,
                     controller: ElasticController,
                     log=print) -> Resumption | None:
    """The survivor's reshard sequence. Returns None when this worker
    is not part of the new world (it should exit cleanly); raises
    SystemExit(RESHARD_FALLBACK_EXIT) when live state cannot be
    carried and the launcher must restart from a checkpoint."""
    import jax

    t0 = time.monotonic()
    # 1. Live state -> canonical host form, BEFORE the old world is
    #    torn down. local_only: a peer may be dead, no collectives.
    try:
        host = trainer.state_to_host(chg.state, local_only=True)
    except RuntimeError as e:
        log(f"[elastic] cannot carry live state ({e}); falling back "
            f"to checkpoint restart")
        raise SystemExit(RESHARD_FALLBACK_EXIT)

    # 2. The launcher's authoritative word on the new world.
    try:
        m = controller.await_membership()
    except TimeoutError as e:
        log(f"[elastic] {e}; falling back to checkpoint restart")
        raise SystemExit(RESHARD_FALLBACK_EXIT)
    controller._known_departed.update(
        int(w) for w in m.get("dropped", []))
    # A rejoining worker is a member again: forget its old departure so
    # a future one re-triggers the fast path.
    controller._known_departed.difference_update(
        int(w) for w in m.get("joiners", []))
    new_rank = m.get("assignments", {}).get(str(controller.worker_id))
    if new_rank is None:
        log(f"[elastic] worker {controller.worker_id} not in epoch "
            f"{m['epoch']}; leaving cleanly")
        return None

    # 3. State beacon for joiners, written by the NEW rank 0 while the
    #    canonical host tree is in hand.
    if new_rank == 0 and m.get("joiners"):
        bdir = beacon_dir(controller.directory, int(m["epoch"]))
        from tpu_ddp.utils import checkpoint as ckpt
        ckpt.save_checkpoint(bdir, host, step=int(host["step"]))
        trainer.sharding_plan().save(bdir)
        with open(os.path.join(bdir, "beacon_meta.json"), "w") as f:
            json.dump({"epoch": chg.epoch, "next_iter": chg.next_iter},
                      f)

    # 4. Re-rendezvous as the new world and rebind every mesh surface.
    teardown_world()
    bootstrap(m["coordinator"], int(m["world"]), int(new_rank))
    from tpu_ddp.parallel.mesh import make_mesh
    mesh = make_mesh()
    trainer.rebind_mesh(mesh)
    state = trainer.state_from_host(host)
    controller.epoch = int(m["epoch"])
    write_ack(controller.directory, controller.epoch,
              controller.worker_id)
    log(f"[elastic] epoch {m['epoch']}: rank "
        f"{controller.worker_id}->{new_rank}, world={m['world']}, "
        f"resharded in {time.monotonic() - t0:.2f}s")
    return Resumption(state=state, rank=int(new_rank),
                      world=int(m["world"]), epoch=chg.epoch,
                      next_iter=chg.next_iter)


def join_world(controller: ElasticController, join_epoch: int,
               deadline_s: float = 120.0) -> dict:
    """A joining process's rendezvous: wait for the membership epoch
    that includes it, then bootstrap into that world. Returns the
    membership record (the caller restores state from the beacon)."""
    t0 = time.monotonic()
    while True:
        m = read_membership(controller.directory)
        if (m is not None and int(m.get("epoch", 0)) >= join_epoch
                and str(controller.worker_id)
                in m.get("assignments", {})):
            break
        if time.monotonic() - t0 > deadline_s:
            raise TimeoutError(
                f"no membership including worker "
                f"{controller.worker_id} at epoch >= {join_epoch}")
        time.sleep(0.05)
    new_rank = int(m["assignments"][str(controller.worker_id)])
    bootstrap(m["coordinator"], int(m["world"]), new_rank)
    controller.epoch = int(m["epoch"])
    write_ack(controller.directory, controller.epoch,
              controller.worker_id)
    return m

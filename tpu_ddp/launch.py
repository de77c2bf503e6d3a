"""Local multi-process launcher — the cluster-in-a-box analogue of the
reference's launch recipe.

The reference is launched by hand on every node of a 4-node cluster with
the same command (reference README.md:8-19)::

    python main.py --num-nodes 4 --rank R --master-ip 10.10.1.1 --master-port 4000

This module automates that loop on ONE host: it spawns ``nproc`` worker
processes, each running a part's ``main.py`` with ``--rank i`` and a shared
``127.0.0.1`` coordinator, so the real multi-process rendezvous path
(``jax.distributed.initialize`` -> cross-process collectives) is exercised
without a cluster — the TPU-native analogue of gloo's multi-process
single-host mode (SURVEY.md §4). On an actual TPU pod each host still runs
its part ``main.py`` directly, exactly like the reference.

CLI::

    python -m tpu_ddp.launch part2b --nproc 4 [--platform cpu]
        [--devices-per-proc 1] [--port auto] [part args...]
"""

from __future__ import annotations

import argparse
import os
import random
import socket
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path

from tpu_ddp.resilience.watchdog import (HEARTBEAT_ENV, STALL_EXIT_CODE,
                                         HeartbeatMonitor)
from tpu_ddp.utils.config import parse_spec_draft

PARTS_DIR = Path(__file__).resolve().parent.parent / "parts"
PARTS = ("part1", "part2a", "part2b", "part3", "part4", "part5")


def find_free_port() -> int:
    """Ask the OS for a free TCP port for the coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@dataclass
class WorkerResult:
    rank: int
    returncode: int
    output: str = ""
    # True when this worker's (nonzero) exit was handled by a live
    # reshard — the survivors carried on, so it does not fail the launch.
    absorbed: bool = False


@dataclass
class LaunchResult:
    workers: list = field(default_factory=list)
    # Exit code of the FIRST rank observed failing — the root cause, not
    # the -9 of bystander ranks reaped afterwards. 0 when all succeeded.
    first_failure: int = 0
    # Number of cluster restarts performed before this (final) attempt —
    # nonzero only for launch_elastic.
    restarts: int = 0
    # Number of live membership epochs (reshard-arounds) this attempt
    # performed instead of restarting — nonzero only under
    # elastic_reshard.
    reshards: int = 0
    # True when the heartbeat watchdog killed this attempt: every rank
    # was alive but none had completed a step within heartbeat_timeout
    # (the hung-collective failure mode — see resilience/watchdog.py).
    stalled: bool = False

    @property
    def returncode(self) -> int:
        if self.first_failure:
            return self.first_failure
        # Fallback (e.g. hand-built results): any nonzero rank fails the
        # launch, including negative signal-kill codes — except workers
        # whose departure a reshard absorbed.
        return next((w.returncode for w in self.workers
                     if w.returncode != 0 and not w.absorbed), 0)

    @property
    def ok(self) -> bool:
        return self.returncode == 0

    def output_of(self, rank: int) -> str:
        for w in self.workers:
            if w.rank == rank:
                return w.output
        raise KeyError(rank)


def _drain(proc, rank: int, sink: list, echo: bool) -> None:
    """Stream one worker's stdout, prefixing lines with its rank."""
    for raw in proc.stdout:
        line = raw.rstrip("\n")
        sink.append(line)
        if echo:
            print(f"[rank {rank}] {line}", flush=True)
    proc.stdout.close()


def launch(
    part: str,
    nproc: int,
    extra_args: list | None = None,
    platform: str = "cpu",
    devices_per_proc: int = 1,
    port: int | None = None,
    env: dict | None = None,
    echo: bool = True,
    timeout: float | None = None,
    heartbeat_timeout: float | None = None,
    heartbeat_dir: str | None = None,
    elastic_reshard: bool = False,
    ack_timeout: float = 120.0,
    rejoin_delay: float = 1.0,
) -> LaunchResult:
    """Run ``nproc`` rank processes of ``parts/<part>/main.py`` and wait.

    Each worker gets ``JAX_PLATFORMS=<platform>`` and (on cpu) a forced
    host-platform device count of ``devices_per_proc``, so a laptop/CI host
    emulates an ``nproc``-node cluster with ``nproc * devices_per_proc``
    total dp slots. Extra env wins over the computed defaults.
    ``platform="tpu"`` is accepted with ``nproc=1`` only (the one child
    drives every local chip) and refused otherwise, before any spawn.
    This process imports jax but never initialises a backend, so the
    child finds the chips free.

    ``heartbeat_timeout`` arms the watchdog: workers inherit
    ``TPU_DDP_HEARTBEAT_DIR`` (a fresh temp dir unless ``heartbeat_dir``
    pins it) and touch a per-rank file each step; once heartbeats exist,
    a cluster whose NEWEST beat is older than the deadline is killed and
    reported with ``stalled=True`` / exit :data:`STALL_EXIT_CODE` —
    catching hung collectives in seconds instead of waiting out
    ``timeout`` (which still bounds never-started clusters).

    ``elastic_reshard`` turns a lost rank from a cluster-wide failure
    into a membership epoch: the launcher writes a ``membership.json``
    protocol directory (resilience/elastic.py), workers join via the
    non-fatal elastic bootstrap, and when a rank dies or stalls while
    others survive, the launcher publishes a shrunken epoch and waits
    for the survivors to reshard their LIVE TrainState around the hole
    (acks within ``ack_timeout``) instead of killing everyone. A rank
    exiting ``HOST_JOIN_EXIT`` is respawned after ``rejoin_delay`` as a
    joiner of a regrown epoch. When a reshard cannot converge (acks
    time out, a survivor exits ``RESHARD_FALLBACK_EXIT``), the attempt
    fails with that code so :func:`launch_elastic` falls back to
    restart-from-checkpoint.
    """
    if nproc < 1:
        raise ValueError("nproc must be >= 1")
    if platform == "tpu" and nproc > 1:
        # Refused before anything is spawned: libtpu gives every process
        # all of the host's chips unless its environment binds it to
        # one, and nothing here does — the first child would take the
        # chips and the others fail or hang at backend start-up.
        raise ValueError(
            "platform='tpu' takes nproc=1: one process drives all of "
            "the host's chips as dp slots (parts/common.py), and "
            "several would each open every chip. On a pod, run one "
            "main.py per host instead")
    if part in PARTS:
        script = PARTS_DIR / part / "main.py"
    elif part.endswith(".py"):
        # Any CLI honouring the reference launch contract
        # (--num-nodes/--rank/--master-ip/--master-port) can be
        # clustered, e.g. examples/lm_train.py. Relative paths resolve
        # against the repo root — the same cwd the workers get — so the
        # call works from any directory.
        p = Path(part)
        script = (p if p.is_absolute() else PARTS_DIR.parent / p).resolve()
    else:
        raise ValueError(f"unknown part {part!r}; available: {PARTS} "
                         "or a path to a *.py CLI")
    if not script.exists():
        raise FileNotFoundError(
            f"{script}: the launcher runs source-checkout CLIs "
            "(parts/ and examples/ are not part of the installed "
            "package)")
    port = port or find_free_port()
    monitor = None
    if heartbeat_timeout is not None:
        hb_dir = heartbeat_dir or tempfile.mkdtemp(prefix="tpu_ddp_hb_")
        monitor = HeartbeatMonitor(hb_dir, nproc, heartbeat_timeout)
    control_dir = None
    if elastic_reshard and nproc > 1:
        from tpu_ddp.resilience import elastic as _el
        # The heartbeat dir doubles as the protocol dir when armed —
        # one place to look at in a post-mortem.
        control_dir = (monitor.directory if monitor is not None
                       else tempfile.mkdtemp(prefix="tpu_ddp_elastic_"))
        _el.reset_control_dir(control_dir)
        _el.write_membership(control_dir, {
            "epoch": 0, "world": nproc, "base_world": nproc,
            "assignments": {str(i): i for i in range(nproc)},
            "coordinator": f"127.0.0.1:{port}",
            "joiners": [], "dropped": []})

    def spawn(rank: int, join_epoch: int | None = None):
        child_env = dict(os.environ)
        child_env["JAX_PLATFORMS"] = platform
        if monitor is not None:
            child_env[HEARTBEAT_ENV] = monitor.directory
        if platform == "cpu":
            # Replace (not append) any inherited forced device count.
            flags = [f for f in child_env.get("XLA_FLAGS", "").split()
                     if "xla_force_host_platform_device_count" not in f]
            flags.append("--xla_force_host_platform_device_count="
                         f"{devices_per_proc}")
            child_env["XLA_FLAGS"] = " ".join(flags)
        if control_dir is not None:
            from tpu_ddp.resilience import elastic as _el
            child_env[_el.ELASTIC_ENV] = "1"
            child_env[_el.ELASTIC_DIR_ENV] = control_dir
            child_env[_el.ELASTIC_RANK_ENV] = str(rank)
            if join_epoch is not None:
                child_env[_el.ELASTIC_JOIN_ENV] = str(join_epoch)
        if env:
            child_env.update(env)
        cmd = [sys.executable, str(script),
               "--num-nodes", str(nproc),
               "--rank", str(rank),
               "--master-ip", "127.0.0.1",
               "--master-port", str(port)] + list(extra_args or [])
        proc = subprocess.Popen(
            cmd, env=child_env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
            cwd=str(PARTS_DIR.parent))
        sink: list = []
        t = threading.Thread(target=_drain, args=(proc, rank, sink, echo),
                             daemon=True)
        t.start()
        return proc, sink, t

    if control_dir is not None:
        return _run_elastic(spawn, nproc, control_dir, monitor, timeout,
                            ack_timeout, rejoin_delay)

    procs = []
    sinks = []
    threads = []
    for rank in range(nproc):
        proc, sink, t = spawn(rank)
        procs.append(proc)
        sinks.append(sink)
        threads.append(t)

    # Poll all ranks concurrently against ONE shared deadline. Sequential
    # proc.wait() calls would hang forever (timeout=None) or for
    # nproc*timeout when one rank dies early and the survivors block in
    # the rendezvous/collective waiting for it.
    deadline = None if timeout is None else time.monotonic() + timeout
    rcs: dict = {}
    first_failure = 0
    while len(rcs) < len(procs):
        for rank, proc in enumerate(procs):
            if rank in rcs:
                continue
            rc = proc.poll()
            if rc is None:
                continue
            rcs[rank] = rc
            if rc != 0:
                first_failure = first_failure or rc
                # A dead rank leaves the others blocked in a collective;
                # reap them now instead of waiting out the timeout.
                for other in procs:
                    if other.poll() is None:
                        other.kill()
        if len(rcs) < len(procs):
            if monitor is not None and not first_failure \
                    and monitor.stalled():
                # Watchdog: every remaining rank is alive but the whole
                # cluster stopped completing steps — a hung collective.
                # Kill it now; launch_elastic will restart with backoff.
                print(f"[launch] heartbeat stall: no step completed in "
                      f"{monitor.timeout:.0f}s — killing the cluster",
                      flush=True)
                for rank, proc in enumerate(procs):
                    if rank not in rcs:
                        proc.kill()
                        rcs[rank] = proc.wait()
                first_failure = STALL_EXIT_CODE
                break
            if deadline is not None and time.monotonic() > deadline:
                # A rank may have exited with a real code (even 0, or a
                # real signal like SIGSEGV) between the last poll and
                # this sweep — record whatever wait() reports, and prefer
                # any such real code as the root cause over the -9 of
                # ranks we killed ourselves (checked only after the whole
                # sweep, so an early hung rank cannot mask a later rank's
                # real failure).
                sweep_real = 0
                for rank, proc in enumerate(procs):
                    if rank not in rcs:
                        proc.kill()
                        rc = proc.wait()
                        rcs[rank] = rc
                        if rc not in (0, -9):
                            sweep_real = sweep_real or rc
                first_failure = first_failure or sweep_real or -9
                break
            time.sleep(0.05)
    result = LaunchResult(first_failure=first_failure,
                          stalled=first_failure == STALL_EXIT_CODE)
    for rank in range(len(procs)):
        result.workers.append(WorkerResult(rank=rank, returncode=rcs[rank]))
    for t in threads:
        t.join(timeout=5)
    for w, sink in zip(result.workers, sinks):
        w.output = "\n".join(sink)
    return result


def _run_elastic(spawn, nproc: int, control_dir: str,
                 monitor: HeartbeatMonitor | None, timeout: float | None,
                 ack_timeout: float, rejoin_delay: float) -> LaunchResult:
    """The elastic poll loop: absorb rank departures into membership
    epochs instead of killing the cluster.

    State machine per event:
    - worker exits 0            -> done (success once all members do)
    - worker exits nonzero,
      survivors remain          -> departure note on its behalf, write
                                   epoch+1 (survivors keep low ranks,
                                   fresh coordinator port), wait for
                                   every survivor's ack
    - exit was HOST_JOIN_EXIT   -> additionally respawn it after
                                   ``rejoin_delay`` as the highest rank
                                   of a regrown epoch (it restores from
                                   the survivors' state beacon)
    - RESHARD_FALLBACK_EXIT, no
      survivors, or acks time
      out                       -> kill everyone, fail the attempt so
                                   launch_elastic restarts from ckpt
    - a rank's heartbeat stalls -> kill THAT rank; its -9 is absorbed
                                   like any other departure (all ranks
                                   stalled -> whole-cluster stall, the
                                   plain watchdog path)
    """
    from tpu_ddp.resilience import elastic as _el

    live = {wid: spawn(wid) for wid in range(nproc)}
    epoch = 0
    reshards = 0
    dropped: list = []
    done: list = []  # (WorkerResult, sink, thread)
    pending_join: list = []  # (due_monotonic, wid)
    deadline = None if timeout is None else time.monotonic() + timeout
    first_failure = 0
    stalled_flag = False

    def record(wid, rc, sink, thread, absorbed=False):
        done.append((WorkerResult(rank=wid, returncode=rc,
                                  absorbed=absorbed), sink, thread))

    def kill_all():
        for wid, (proc, sink, t) in list(live.items()):
            if proc.poll() is None:
                proc.kill()
            record(wid, proc.wait(), sink, t)
            del live[wid]

    def write_epoch(joiner=None):
        nonlocal epoch, reshards
        epoch += 1
        reshards += 1
        # Survivors keep the low ranks; a joiner takes the highest —
        # rank 0 (coordination service host + beacon writer) is always
        # an already-running survivor.
        order = sorted(live)
        if joiner is not None and joiner not in live:
            order.append(joiner)
        _el.write_membership(control_dir, {
            "epoch": epoch, "world": len(order), "base_world": nproc,
            "assignments": {str(w): i for i, w in enumerate(order)},
            "coordinator": f"127.0.0.1:{find_free_port()}",
            "joiners": [] if joiner is None else [joiner],
            "dropped": sorted(dropped)})
        return order

    def await_acks(members):
        stop = time.monotonic() + ack_timeout
        while time.monotonic() < stop:
            if all(os.path.exists(_el.ack_path(control_dir, epoch, w))
                   for w in members):
                if monitor is not None:
                    # Survivors paused beating to recompile; fresh grace.
                    monitor.reset_grace()
                return True
            # A member dying mid-reshard (cascade) fails the epoch.
            if any(w in live and live[w][0].poll() is not None
                   for w in members):
                return False
            time.sleep(0.05)
        return False

    while (live or pending_join) and not first_failure:
        now = time.monotonic()
        # 1. Respawn due joiners into a regrown epoch.
        for item in [x for x in pending_join if x[0] <= now]:
            pending_join.remove(item)
            wid = item[1]
            if not live:
                first_failure = _el.HOST_JOIN_EXIT
                break
            _el.clear_departure(control_dir, wid)
            if wid in dropped:
                dropped.remove(wid)
            members = write_epoch(joiner=wid)
            live[wid] = spawn(wid, join_epoch=epoch)
            print(f"[launch] epoch {epoch}: worker {wid} rejoining, "
                  f"world={len(members)}", flush=True)
            if not await_acks(members):
                print("[launch] rejoin epoch failed to converge; "
                      "falling back to restart", flush=True)
                first_failure = _el.RESHARD_FALLBACK_EXIT
                kill_all()
                break
        if first_failure:
            break
        # 2. Reap exits.
        for wid in sorted(live):
            proc, sink, t = live[wid]
            rc = proc.poll()
            if rc is None:
                continue
            del live[wid]
            if rc == 0:
                record(wid, 0, sink, t)
                continue
            if rc == _el.RESHARD_FALLBACK_EXIT or not live:
                # A survivor that cannot carry its live state, or the
                # last member dying: nothing to reshard around.
                record(wid, rc, sink, t)
                first_failure = rc
                kill_all()
                break
            reason = {_el.HOST_LOSS_EXIT: "host-loss",
                      _el.HOST_JOIN_EXIT: "host-join"}.get(
                          rc, f"rc={rc}")
            _el.announce_departure(control_dir, wid, reason)
            record(wid, rc, sink, t, absorbed=True)
            dropped.append(wid)
            members = write_epoch()
            print(f"[launch] epoch {epoch}: worker {wid} left "
                  f"({reason}); resharding onto {len(members)} "
                  f"survivor(s)", flush=True)
            if not await_acks(members):
                print("[launch] reshard failed to converge; falling "
                      "back to restart", flush=True)
                first_failure = _el.RESHARD_FALLBACK_EXIT
                kill_all()
                break
            if rc == _el.HOST_JOIN_EXIT:
                pending_join.append((time.monotonic() + rejoin_delay,
                                     wid))
        if first_failure:
            break
        # 3. Per-rank stalls: kill the wedged rank, absorb it above.
        if monitor is not None and live:
            stalled = monitor.stalled_ranks(ranks=sorted(live))
            if stalled and len(stalled) == len(live):
                print(f"[launch] heartbeat stall on every live rank "
                      f"({monitor.timeout:.0f}s) — killing the cluster",
                      flush=True)
                first_failure = STALL_EXIT_CODE
                stalled_flag = True
                kill_all()
                break
            for wid in stalled:
                print(f"[launch] rank {wid} heartbeat stalled "
                      f"({monitor.timeout:.0f}s); killing it and "
                      f"resharding around it", flush=True)
                live[wid][0].kill()
        # 4. Overall deadline still bounds the attempt.
        if deadline is not None and now > deadline:
            first_failure = -9
            kill_all()
            break
        if live or pending_join:
            time.sleep(0.05)

    result = LaunchResult(first_failure=first_failure,
                          reshards=reshards, stalled=stalled_flag)
    for w, sink, t in done:
        t.join(timeout=5)
        w.output = "\n".join(sink)
        result.workers.append(w)
    result.workers.sort(key=lambda w: w.rank)
    return result


def backoff_delay(attempt: int, floor: float = 1.0, cap: float = 60.0,
                  rng: random.Random | None = None) -> float:
    """Seconds to wait before restart ``attempt`` (1-based).

    Exponential from ``floor`` (doubling per attempt, capped at ``cap``)
    plus 0–25% multiplicative jitter: a flaky shared dependency that
    fails N clusters at once must not have them all re-stampede it in
    lockstep. ``floor <= 0`` disables the wait entirely (tests).
    ``rng`` injects a seeded generator for deterministic schedules.
    """
    if attempt < 1:
        raise ValueError(f"attempt is 1-based, got {attempt}")
    if floor <= 0:
        return 0.0
    base = min(cap, floor * (2.0 ** (attempt - 1)))
    return base * (1.0 + (rng or random).uniform(0.0, 0.25))


def launch_elastic(
    part: str,
    nproc: int,
    max_restarts: int = 0,
    extra_args: list | None = None,
    min_restart_interval: float = 1.0,
    restart_window: float | None = None,
    backoff_cap: float = 60.0,
    **kwargs,
) -> LaunchResult:
    """:func:`launch` with elastic recovery — the failure-handling layer
    the reference lacks entirely (SURVEY.md §5: a dead gloo rank just
    hangs the cluster). On failure the whole cluster is respawned (fresh
    coordinator port) up to ``max_restarts`` times; when the part was
    given a ``--ckpt-dir`` and a checkpoint exists, retries append
    ``--resume`` so training continues from the last saved step instead
    of restarting from scratch.

    Restarts back off exponentially from ``min_restart_interval``
    (doubling per attempt up to ``backoff_cap``, with jitter —
    :func:`backoff_delay`), so a persistent failure burns budget slowly
    instead of crash-looping. ``restart_window`` makes the budget a
    SLIDING window: only restarts within the last ``restart_window``
    seconds count against ``max_restarts``, so a long healthy run that
    hits one preemption a day restarts indefinitely while a crash loop
    still stops after ``max_restarts`` attempts. ``None`` keeps the
    lifetime budget. Extra ``kwargs`` reach :func:`launch` — pass
    ``heartbeat_timeout`` to also arm the stall watchdog per attempt.
    """
    if max_restarts < 0:
        raise ValueError("max_restarts must be >= 0")
    extra = list(extra_args or [])
    ckpt_dir = None
    for idx, tok in enumerate(extra):
        if tok == "--ckpt-dir":
            if idx + 1 >= len(extra):
                raise ValueError("--ckpt-dir requires a value")
            ckpt_dir = extra[idx + 1]
        elif tok.startswith("--ckpt-dir="):
            ckpt_dir = tok.split("=", 1)[1]
    restart_times: deque = deque()  # monotonic stamps of restarts done
    attempt = 0
    while True:
        args = list(extra)
        if attempt > 0 and ckpt_dir and "--resume" not in args:
            from tpu_ddp.utils.checkpoint import latest_step
            if latest_step(ckpt_dir) is not None:
                args.append("--resume")
        res = launch(part, nproc, extra_args=args, **kwargs)
        res.restarts = attempt
        if res.ok:
            break
        # Budget for one more restart? Under a sliding window, stamps
        # older than the window no longer count.
        now = time.monotonic()
        if restart_window is not None:
            while restart_times and now - restart_times[0] \
                    > restart_window:
                restart_times.popleft()
            if len(restart_times) >= max_restarts:
                break
        elif attempt >= max_restarts:
            break
        attempt += 1
        delay = backoff_delay(attempt, floor=min_restart_interval,
                              cap=backoff_cap)
        why = "stalled" if res.stalled else f"rc={res.returncode}"
        print(f"[launch] attempt failed ({why}); restart {attempt} in "
              f"{delay:.2f}s", flush=True)
        if delay > 0:
            time.sleep(delay)
        restart_times.append(time.monotonic())
        kwargs.pop("port", None)  # fresh coordinator port per attempt
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m tpu_ddp.launch",
        description="spawn an N-process local cluster running one part")
    p.add_argument("part", metavar="part|script.py",
                   help=f"one of {', '.join(PARTS)}, or a path to a "
                        "*.py CLI honouring the launch contract")
    p.add_argument("--nproc", type=int, required=True,
                   help="number of rank processes (the --num-nodes value)")
    p.add_argument("--platform", default="cpu",
                   help="JAX platform for workers (default cpu; tpu "
                        "only with --nproc 1: the one worker drives "
                        "every local chip)")
    p.add_argument("--devices-per-proc", type=int, default=1,
                   help="forced CPU device count per worker (cpu only)")
    p.add_argument("--port", type=int, default=None,
                   help="coordinator port (default: pick a free one)")
    p.add_argument("--max-restarts", type=int, default=0,
                   help="respawn the cluster up to N times on failure, "
                        "resuming from --ckpt-dir when possible")
    p.add_argument("--min-restart-interval", type=float, default=1.0,
                   help="backoff floor in seconds before the first "
                        "restart; doubles per attempt with jitter "
                        "(<= 0 restarts immediately)")
    p.add_argument("--restart-window", type=float, default=None,
                   help="count only restarts within the last N seconds "
                        "against --max-restarts (default: lifetime)")
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   help="kill + restart a cluster whose ranks all stop "
                        "completing steps for N seconds (stall watchdog)")
    p.add_argument("--dispatch-depth", type=int, default=None,
                   help="train steps kept in flight per worker before a "
                        "forced host sync (async dispatch pipeline, "
                        "tpu_ddp/train/pipeline.py); 0 = synchronous "
                        "loop. Sets TPU_DDP_DISPATCH_DEPTH for every "
                        "rank (default: the workers' config default)")
    p.add_argument("--grad-compress", default=None,
                   choices=("none", "bf16", "int8", "int8-noef"),
                   help="gradient wire format for the sync collectives "
                        "(tpu_ddp/parallel/compress.py): bf16 halves, "
                        "int8 ~quarters the bytes on the wire (int8 "
                        "carries an error-feedback residual; int8-noef "
                        "is the ablation without it). Sets "
                        "TPU_DDP_GRAD_COMPRESS for every rank")
    p.add_argument("--pp-schedule", default=None,
                   choices=("gpipe", "1f1b", "interleaved", "zerobubble"),
                   help="pipeline tick schedule for the pp rung "
                        "(tpu_ddp/parallel/pipeline.py): gpipe (AD of "
                        "the forward scan), 1f1b (O(pp) activation "
                        "residency), interleaved (virtual stages, "
                        "bubble / pp_virtual) or zerobubble (B-weight "
                        "fills the cooldown). Sets TPU_DDP_PP_SCHEDULE "
                        "for every rank")
    p.add_argument("--pp-microbatches", type=int, default=None,
                   help="microbatches per pipeline step (0 = auto, one "
                        "per stage). Sets TPU_DDP_PP_MICROBATCHES for "
                        "every rank")
    p.add_argument("--pp-virtual", type=int, default=None,
                   help="virtual stage chunks per physical stage "
                        "(interleaved schedule only; needs num_layers "
                        "divisible by pp*pp_virtual). Sets "
                        "TPU_DDP_PP_VIRTUAL for every rank")
    p.add_argument("--remat", default=None,
                   choices=("none", "blocks", "conv_stages", "dots"),
                   help="activation rematerialization policy "
                        "(tpu_ddp/memory/): which model stages "
                        "recompute in the backward pass instead of "
                        "saving activations — 'blocks' (per residual/"
                        "transformer block), 'conv_stages' (per "
                        "resolution stage, conv families), 'dots' "
                        "(save matmul outputs only). Sets "
                        "TPU_DDP_REMAT for every rank")
    p.add_argument("--act-dtype", default=None,
                   choices=("compute", "bf16", "f32"),
                   help="saved-residual dtype at remat-stage "
                        "boundaries (tpu_ddp/memory/): what autodiff "
                        "stores between forward and backward; stage "
                        "arithmetic stays in compute_dtype. Sets "
                        "TPU_DDP_ACT_DTYPE for every rank")
    p.add_argument("--overlap", action="store_true",
                   help="bucketize gradients in reverse-autodiff order "
                        "and issue each bucket's collective from inside "
                        "the backward pass (torch DDP's reducer; "
                        "tpu_ddp/parallel/overlap.py), with the sharded "
                        "weight update on the all_reduce/fused rungs. "
                        "Sets TPU_DDP_OVERLAP for every rank")
    p.add_argument("--bucket-mb", type=int, default=None,
                   help="bucket payload target in MiB for --overlap "
                        "(torch DDP's bucket_cap_mb; default 25). Sets "
                        "TPU_DDP_BUCKET_MB for every rank")
    p.add_argument("--elastic-reshard", action="store_true",
                   help="on membership change (a rank lost, stalled, "
                        "or rejoining) reshard the survivors' LIVE "
                        "TrainState onto a rebuilt mesh instead of "
                        "killing the cluster "
                        "(tpu_ddp/resilience/elastic.py + "
                        "parallel/redistribute.py); failed reshards "
                        "still fall back to --max-restarts checkpoint "
                        "recovery. Sets TPU_DDP_ELASTIC_RESHARD for "
                        "every rank")
    p.add_argument("--fleet-health", default=None, choices=("0", "1"),
                   help="replica health tracking + deterministic "
                        "request migration in the serving Router "
                        "(tpu_ddp/fleet/router.py); '0' = fail-fast. "
                        "Sets TPU_DDP_FLEET_HEALTH for every rank")
    p.add_argument("--fleet-probe-backoff-ms", type=float, default=None,
                   help="initial probe-re-admission backoff for an "
                        "unhealthy replica, doubling per consecutive "
                        "failure (default 200). Sets "
                        "TPU_DDP_FLEET_HEALTH_BACKOFF_MS for every rank")
    p.add_argument("--fleet-step-deadline-ms", type=float, default=None,
                   help="per-replica step deadline; a step exceeding "
                        "it counts as a failure (0 disables). Sets "
                        "TPU_DDP_FLEET_HEALTH_DEADLINE_MS for every "
                        "rank")
    p.add_argument("--fleet-retry-budget", type=int, default=None,
                   help="migrations allowed per request before the "
                        "Router sheds it (default 3). Sets "
                        "TPU_DDP_FLEET_RETRY_BUDGET for every rank")
    p.add_argument("--serve-queue-limit", type=int, default=None,
                   help="bounded serving admission queue; submits "
                        "beyond this many waiting requests are shed "
                        "(0 = unbounded). Sets TPU_DDP_SERVE_QUEUE_LIMIT "
                        "for every rank")
    p.add_argument("--serve-shed-ms", type=float, default=None,
                   help="shed a queued request that has not started "
                        "prefill after this many ms (0 disables). Sets "
                        "TPU_DDP_SERVE_SHED_MS for every rank")
    p.add_argument("--fleet-autoscale", default=None, choices=("0", "1"),
                   help="autoscaling replica lifecycle control plane "
                        "(tpu_ddp/fleet/autoscale.py): scale-up boots "
                        "replicas from the publisher's full-push path, "
                        "scale-down drains via bitwise continuation "
                        "migration. Sets TPU_DDP_FLEET_AUTOSCALE for "
                        "every rank")
    p.add_argument("--scale-cooldown-ms", type=float, default=None,
                   help="minimum ms between autoscaler actions "
                        "(default 1000); with hysteresis, what keeps a "
                        "flash crowd from thrashing the fleet. Sets "
                        "TPU_DDP_SCALE_COOLDOWN_MS for every rank")
    p.add_argument("--tenant-classes", default=None,
                   help="SLO classes for multi-tenant serving: comma-"
                        "separated name=weight[:deadline_ms[:token_"
                        "budget]] (e.g. 'gold=3,bronze=1'); empty = "
                        "single-tenant FIFO. Sets "
                        "TPU_DDP_TENANT_CLASSES for every rank")
    p.add_argument("--publish-every", type=int, default=None,
                   help="publish a versioned weight update to "
                        "subscribed serving engines every this many "
                        "trainer steps (0 = off). Sets "
                        "TPU_DDP_PUBLISH_EVERY for every rank")
    p.add_argument("--publish-wire", default=None,
                   choices=("none", "bf16", "int8", "sparse"),
                   help="wire format for pushed weight deltas "
                        "(tpu_ddp/publish/): dense f32, bf16, "
                        "error-feedback int8, or lossless sparse "
                        "(zero-chunk elision — the MoE expert-delta "
                        "wire). Sets TPU_DDP_PUBLISH_WIRE for every "
                        "rank")
    p.add_argument("--publish-max-staleness", type=int, default=None,
                   help="steps the trainer may run ahead of the "
                        "slowest subscriber before publishing blocks "
                        "(0 = unbounded). Sets "
                        "TPU_DDP_PUBLISH_MAX_STALENESS for every rank")
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative decoding: proposals verified per "
                        "serving engine step (0 = off, the one-token "
                        "baseline; tpu_ddp/serve/speculative.py). Sets "
                        "TPU_DDP_SPEC_K for every rank")
    p.add_argument("--spec-draft", default=None,
                   help="draft family for speculation: 'self-<j>' "
                        "(early exit over the target's first j "
                        "blocks; 'self-1' is the default) or 'quant' "
                        "(full-depth int8 twin). Sets "
                        "TPU_DDP_SPEC_DRAFT for every rank")
    p.add_argument("--decode-quant", default=None,
                   choices=("none", "int8"),
                   help="weight-only int8 decode compute "
                        "(tpu_ddp/ops/quant.py): per-channel "
                        "quantization of every decode-path projection "
                        "at engine construction. Sets "
                        "TPU_DDP_DECODE_QUANT for every rank")
    p.add_argument("--kv-tiers", type=int, default=None,
                   choices=(1, 2, 3),
                   help="tiered KV pool (tpu_ddp/serve/kv_pool.py): "
                        "1 = single-tier, 2 adds an in-HBM quantized "
                        "cold tier, 3 adds host-memory spill behind "
                        "it. Sets TPU_DDP_KV_TIERS for every rank")
    p.add_argument("--kv-cold-dtype", default=None,
                   choices=("int8", "bf16"),
                   help="cold-page codec for --kv-tiers >= 2: "
                        "per-token-row int8 or a bf16 downcast "
                        "(lossless under a bf16 hot cache dtype). "
                        "Sets TPU_DDP_KV_COLD_DTYPE for every rank")
    p.add_argument("--cp-prefill", default=None,
                   choices=("off", "ring", "ulysses"),
                   help="context-parallel chunked prefill "
                        "(tpu_ddp/serve/long_context.py): shard each "
                        "prefill chunk over the serving mesh's sp "
                        "axis. Sets TPU_DDP_CP_PREFILL for every rank")
    p.add_argument("--moe-experts", type=int, default=None,
                   help="experts per MoE MLP layer (0 = dense; "
                        "tpu_ddp/parallel/moe.py). Sets "
                        "TPU_DDP_MOE_EXPERTS for every rank")
    p.add_argument("--moe-top-k", type=int, default=None,
                   help="routed experts per token (1 = Switch, 2 = "
                        "GShard). Sets TPU_DDP_MOE_TOP_K for every "
                        "rank")
    p.add_argument("--moe-capacity", type=float, default=None,
                   help="expert capacity factor: slots per expert = "
                        "ceil(T * capacity * top_k / E); higher = "
                        "fewer dropped tokens, more padded compute. "
                        "Sets TPU_DDP_MOE_CAPACITY for every rank")
    p.add_argument("--diloco-h", type=int, default=None,
                   help="DiLoCo inner steps per outer round (0 = off; "
                        "tpu_ddp/train/outer.py): each group runs H "
                        "local steps, only the outer pseudo-gradient "
                        "exchange crosses groups. Sets "
                        "TPU_DDP_DILOCO_H for every rank")
    p.add_argument("--diloco-outer-lr", type=float, default=None,
                   help="outer Nesterov-momentum learning rate over "
                        "pseudo-gradients (1 with zero momentum = "
                        "plain parameter averaging). Sets "
                        "TPU_DDP_DILOCO_OUTER_LR for every rank")
    p.add_argument("--diloco-outer-momentum", type=float, default=None,
                   help="outer Nesterov momentum coefficient in "
                        "[0, 1). Sets TPU_DDP_DILOCO_OUTER_MOMENTUM "
                        "for every rank")
    p.add_argument("--diloco-outer-wire", default=None,
                   choices=("none", "bf16", "int8", "sparse"),
                   help="cross-group pseudo-gradient wire format (the "
                        "publish/ delta codec vocabulary; 'none' ships "
                        "bitwise full tensors). Sets "
                        "TPU_DDP_DILOCO_OUTER_WIRE for every rank")
    p.add_argument("--autotune", default=None,
                   choices=("off", "cached", "search"),
                   help="perf-knob autotuning (tpu_ddp/tune/): 'cached' "
                        "applies a previously searched tuning for this "
                        "workload fingerprint, 'search' runs measured "
                        "trials and persists the winner (single-process "
                        "only; multi-process ranks fall back to 'cached' "
                        "semantics). Sets TPU_DDP_AUTOTUNE for every "
                        "rank")
    p.add_argument("--audit", default=None,
                   choices=("off", "warn", "error"),
                   help="construction-time graph audit "
                        "(tpu_ddp/analysis/): statically check buffer "
                        "donation and collective precision of every "
                        "rank's compiled step programs before training "
                        "starts; 'error' fails construction on a "
                        "finding. Sets TPU_DDP_AUDIT for every rank")
    args, extra = p.parse_known_args(argv)
    env = {}
    if args.dispatch_depth is not None:
        if args.dispatch_depth < 0:
            p.error(f"--dispatch-depth must be >= 0, "
                    f"got {args.dispatch_depth}")
        env["TPU_DDP_DISPATCH_DEPTH"] = str(args.dispatch_depth)
    if args.grad_compress is not None:
        env["TPU_DDP_GRAD_COMPRESS"] = args.grad_compress
    if args.pp_schedule is not None:
        env["TPU_DDP_PP_SCHEDULE"] = args.pp_schedule
    if args.pp_microbatches is not None:
        if args.pp_microbatches < 0:
            p.error(f"--pp-microbatches must be >= 0, "
                    f"got {args.pp_microbatches}")
        env["TPU_DDP_PP_MICROBATCHES"] = str(args.pp_microbatches)
    if args.pp_virtual is not None:
        if args.pp_virtual < 1:
            p.error(f"--pp-virtual must be >= 1, got {args.pp_virtual}")
        env["TPU_DDP_PP_VIRTUAL"] = str(args.pp_virtual)
    if args.remat is not None:
        env["TPU_DDP_REMAT"] = args.remat
    if args.act_dtype is not None:
        env["TPU_DDP_ACT_DTYPE"] = args.act_dtype
    if args.fleet_health is not None:
        env["TPU_DDP_FLEET_HEALTH"] = args.fleet_health
    if args.fleet_probe_backoff_ms is not None:
        if args.fleet_probe_backoff_ms <= 0:
            p.error(f"--fleet-probe-backoff-ms must be > 0, "
                    f"got {args.fleet_probe_backoff_ms}")
        env["TPU_DDP_FLEET_HEALTH_BACKOFF_MS"] = \
            str(args.fleet_probe_backoff_ms)
    if args.fleet_step_deadline_ms is not None:
        if args.fleet_step_deadline_ms < 0:
            p.error(f"--fleet-step-deadline-ms must be >= 0, "
                    f"got {args.fleet_step_deadline_ms}")
        env["TPU_DDP_FLEET_HEALTH_DEADLINE_MS"] = \
            str(args.fleet_step_deadline_ms)
    if args.fleet_retry_budget is not None:
        if args.fleet_retry_budget < 0:
            p.error(f"--fleet-retry-budget must be >= 0, "
                    f"got {args.fleet_retry_budget}")
        env["TPU_DDP_FLEET_RETRY_BUDGET"] = str(args.fleet_retry_budget)
    if args.serve_queue_limit is not None:
        if args.serve_queue_limit < 0:
            p.error(f"--serve-queue-limit must be >= 0, "
                    f"got {args.serve_queue_limit}")
        env["TPU_DDP_SERVE_QUEUE_LIMIT"] = str(args.serve_queue_limit)
    if args.serve_shed_ms is not None:
        if args.serve_shed_ms < 0:
            p.error(f"--serve-shed-ms must be >= 0, "
                    f"got {args.serve_shed_ms}")
        env["TPU_DDP_SERVE_SHED_MS"] = str(args.serve_shed_ms)
    if args.fleet_autoscale is not None:
        env["TPU_DDP_FLEET_AUTOSCALE"] = args.fleet_autoscale
    if args.scale_cooldown_ms is not None:
        if args.scale_cooldown_ms <= 0:
            p.error(f"--scale-cooldown-ms must be > 0, "
                    f"got {args.scale_cooldown_ms}")
        env["TPU_DDP_SCALE_COOLDOWN_MS"] = str(args.scale_cooldown_ms)
    if args.tenant_classes is not None:
        for ent in args.tenant_classes.split(","):
            if ent.strip() and "=" not in ent:
                p.error(f"--tenant-classes entry {ent.strip()!r}: "
                        "expected name=weight[:deadline_ms[:token_"
                        "budget]]")
        env["TPU_DDP_TENANT_CLASSES"] = args.tenant_classes
    if args.publish_every is not None:
        if args.publish_every < 0:
            p.error(f"--publish-every must be >= 0, "
                    f"got {args.publish_every}")
        env["TPU_DDP_PUBLISH_EVERY"] = str(args.publish_every)
    if args.publish_wire is not None:
        env["TPU_DDP_PUBLISH_WIRE"] = args.publish_wire
    if args.publish_max_staleness is not None:
        if args.publish_max_staleness < 0:
            p.error(f"--publish-max-staleness must be >= 0, "
                    f"got {args.publish_max_staleness}")
        env["TPU_DDP_PUBLISH_MAX_STALENESS"] = \
            str(args.publish_max_staleness)
    if args.spec_k is not None:
        if args.spec_k < 0:
            p.error(f"--spec-k must be >= 0, got {args.spec_k}")
        env["TPU_DDP_SPEC_K"] = str(args.spec_k)
    if args.spec_draft is not None:
        try:
            parse_spec_draft(args.spec_draft)
        except ValueError as e:
            p.error(f"--spec-draft: {e}")
        env["TPU_DDP_SPEC_DRAFT"] = args.spec_draft
    if args.decode_quant is not None:
        env["TPU_DDP_DECODE_QUANT"] = args.decode_quant
    if args.kv_tiers is not None:
        env["TPU_DDP_KV_TIERS"] = str(args.kv_tiers)
    if args.kv_cold_dtype is not None:
        env["TPU_DDP_KV_COLD_DTYPE"] = args.kv_cold_dtype
    if args.cp_prefill is not None:
        env["TPU_DDP_CP_PREFILL"] = args.cp_prefill
    if args.moe_experts is not None:
        if args.moe_experts < 0:
            p.error(f"--moe-experts must be >= 0, got "
                    f"{args.moe_experts}")
        env["TPU_DDP_MOE_EXPERTS"] = str(args.moe_experts)
    if args.moe_top_k is not None:
        if args.moe_top_k < 1:
            p.error(f"--moe-top-k must be >= 1, got {args.moe_top_k}")
        env["TPU_DDP_MOE_TOP_K"] = str(args.moe_top_k)
    if args.moe_capacity is not None:
        if not args.moe_capacity > 0:
            p.error(f"--moe-capacity must be > 0, got "
                    f"{args.moe_capacity}")
        env["TPU_DDP_MOE_CAPACITY"] = str(args.moe_capacity)
    if args.diloco_h is not None:
        if args.diloco_h < 0:
            p.error(f"--diloco-h must be >= 0, got {args.diloco_h}")
        env["TPU_DDP_DILOCO_H"] = str(args.diloco_h)
    if args.diloco_outer_lr is not None:
        if not args.diloco_outer_lr > 0:
            p.error(f"--diloco-outer-lr must be > 0, got "
                    f"{args.diloco_outer_lr}")
        env["TPU_DDP_DILOCO_OUTER_LR"] = str(args.diloco_outer_lr)
    if args.diloco_outer_momentum is not None:
        if not 0.0 <= args.diloco_outer_momentum < 1.0:
            p.error(f"--diloco-outer-momentum must be in [0, 1), got "
                    f"{args.diloco_outer_momentum}")
        env["TPU_DDP_DILOCO_OUTER_MOMENTUM"] = str(
            args.diloco_outer_momentum)
    if args.diloco_outer_wire is not None:
        env["TPU_DDP_DILOCO_OUTER_WIRE"] = args.diloco_outer_wire
    if args.autotune is not None:
        env["TPU_DDP_AUTOTUNE"] = args.autotune
    if args.audit is not None:
        env["TPU_DDP_AUDIT"] = args.audit
    if args.overlap:
        env["TPU_DDP_OVERLAP"] = "1"
    if args.bucket_mb is not None:
        if args.bucket_mb <= 0:
            p.error(f"--bucket-mb must be > 0, got {args.bucket_mb}")
        env["TPU_DDP_BUCKET_MB"] = str(args.bucket_mb)
    if args.elastic_reshard:
        env["TPU_DDP_ELASTIC_RESHARD"] = "1"
    env = env or None
    try:
        res = launch_elastic(args.part, args.nproc,
                             max_restarts=args.max_restarts,
                             extra_args=extra, env=env,
                             min_restart_interval=args.min_restart_interval,
                             restart_window=args.restart_window,
                             heartbeat_timeout=args.heartbeat_timeout,
                             elastic_reshard=args.elastic_reshard,
                             platform=args.platform,
                             devices_per_proc=args.devices_per_proc,
                             port=args.port)
    except (ValueError, FileNotFoundError) as e:
        p.error(str(e))  # clean usage error, not a traceback
    for w in res.workers:
        print(f"[launch] rank {w.rank} exited {w.returncode}")
    if res.stalled:
        print("[launch] final attempt killed by the heartbeat watchdog")
    if res.reshards:
        print(f"[launch] absorbed {res.reshards} membership epoch(s) "
              "by live resharding")
    if res.restarts:
        print(f"[launch] recovered after {res.restarts} restart(s)")
    return res.returncode


if __name__ == "__main__":
    sys.exit(main())

"""Headline benchmark: VGG-11 CIFAR-10 training throughput on one TPU chip,
with MFU accounting and sub-benchmarks for every BASELINE.json config.

Protocol: the reference's measurement fixture averages iterations 1..39
with iteration 0 discarded as warm-up (reference part1/main.py:66,86-91;
BASELINE.md). We keep that shape — one warm compile step, then
``timed_iters`` steps averaged — with two recorded variants:

- the HEADLINE (round 5) is the DIFFERENCED MULTI-STEP protocol: a
  2-call and a 10-call window of a 16-step ``lax.scan`` are timed and
  differenced, which cancels the fixed per-call readback and leaves
  chip time;
- the secondary (``extra.chained_dispatch``) times the steps as a
  CHAINED DISPATCH with a single final readback rather than a host sync
  per iteration:

- each step donates and consumes the previous step's state, so the steps
  execute strictly sequentially on the chip (data dependency, not host
  discipline), and reading the final loss value back to host bounds the
  completion of every timed step;
- a per-iteration host sync would be reference-faithful but adds the
  host round trip to every step of a few milliseconds (recorded in
  ``extra.end_to_end_iter_s``). Round 1's recorded 723k img/s suffered
  the inverse artifact — async dispatch never synchronized, so the
  timer saw only dispatch cost. The chained protocol is immune to both
  failure modes.

Batches are staged on device before the clock starts (4 distinct batches,
cycled); the end-to-end number including host->device transfer of raw
uint8 per step is recorded separately for the headline config.

Baseline (BASELINE.md, derived throughput): the reference's best
configuration — part3 torch-DDP on FOUR CPU nodes — reaches ~386 img/s
aggregate. ``vs_baseline`` is our single-chip images/sec divided by that
386 img/s. Since the reference hardware is four 2022 CPU nodes, the ratio
proves capability, not efficiency; efficiency is what the MFU block in
``extra`` reports: analytic model FLOPs/step (tpu_ddp/utils/flops.py),
the chip's bf16 peak, achieved TFLOP/s, and their ratio, for all three
model-family configs.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "extra"};
``extra.configs`` holds the resnet50/transformer sub-results,
``extra.flash_attention_delta`` the Pallas-flash vs jnp-attention delta,
``extra.batch_sweep`` the headline model's throughput vs batch size,
``extra.collectives`` the ICI microbench (when >1 device is attached),
and ``extra.overlap`` the bucketized-collectives probe (fused rung vs
25 MB buckets + sharded update, with the compiled-HLO overlap verdict).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np


def _mfu_block(flops_fwd: int | None, avg_iter_s: float, jitted=None,
               lower_args: tuple | None = None) -> dict:
    import jax

    from tpu_ddp.utils import flops as F

    xf = None
    if jitted is not None and lower_args is not None:
        xf = F.xla_flops(jitted, *lower_args)
    train = F.train_flops(flops_fwd) if flops_fwd is not None else None
    return F.mfu_fields(train, avg_iter_s, jax.devices()[0],
                        xla_flops_per_step=xf)


def _spread_pct(samples: list) -> float:
    med = float(np.median(samples))
    return (100.0 * (max(samples) - min(samples)) / med if med > 0
            else float("inf"))


def _gated_samples(one_sample, windows: int,
                   spread_gate_pct: float = 5.0) -> tuple:
    """(median of the last ``windows`` samples, all samples) where
    ``one_sample()`` produces one timing sample. The ONE spread-gate
    implementation (round-4 verdict item 3), shared by the chained and
    the multi-step protocols: take ``windows`` samples; while the most
    recent ``windows`` of them spread wider than the gate (a host
    hiccup landed inside a window), keep sampling up to 3x the asked
    count. Every sample stays recorded; the median comes from the
    recent slice so an early transient cannot skew a committed number.
    ``one_sample`` may return None to discard a corrupted measurement
    (e.g. a nonpositive differenced window) — discards do not count
    toward the sample list but do count toward the 3x attempt cap."""
    windows = max(1, windows)
    samples = []
    attempts = 0

    def take():
        nonlocal attempts
        attempts += 1
        s = one_sample()
        if s is not None and s > 0:
            samples.append(s)

    while len(samples) < windows and attempts < 3 * windows + 2:
        take()
    while (attempts < 3 * windows + 2 and windows > 1
           and _spread_pct(samples[-windows:]) > spread_gate_pct):
        take()
    if not samples:
        raise RuntimeError("every timing sample was discarded as "
                           "corrupted (nonpositive)")
    used = samples[-windows:]
    return float(np.median(used)), samples


def _chained_avg_s(step, state, staged, timed_iters: int,
                   windows: int = 3, spread_gate_pct: float = 5.0):
    """(median avg s/step, state, per-window samples) over ``windows``
    consecutive chained windows of ``timed_iters`` steps each.

    One warm step (compile + first execution — the reference's discarded
    iteration 0) synchronizes via a value readback; each timed window then
    dispatches back-to-back, serialized on-chip by the donated-state data
    dependency, with a loss readback bounding the window's completion.

    Round-3 verdict item 2: a single window cannot distinguish host
    noise from a real regression, so every recorded
    number is the MEDIAN of >= 3 windows with all samples kept in
    ``extra.samples``. Round-4 verdict item 3 (the spread gate): when
    the window spread exceeds ``spread_gate_pct`` — a host hiccup
    landed inside a window — keep taking windows (up to 3x the asked
    count) until the spread over the most recent ``windows`` samples
    passes the gate; every sample taken stays recorded, and the median
    is computed over that passing (or final) recent slice so a
    transient early hiccup cannot skew a committed number.
    """
    import jax  # noqa: F401  (backend must be live)

    state, loss = step(state, *staged[0])
    np.asarray(loss)  # warm-up barrier (iteration 0, discarded)
    # Settle: the first post-compile executions can carry a one-time
    # runtime transient (program upload/initialization); a short
    # discarded burst keeps it out of the steady-state window, in the
    # spirit of the reference's discarded iteration 0
    # (part1/main.py:86-91).
    for i in range(3):
        state, loss = step(state, *staged[i % len(staged)])
    np.asarray(loss)

    def one_window():
        nonlocal state
        t0 = time.perf_counter()
        for i in range(timed_iters):
            state, loss = step(state, *staged[i % len(staged)])
        np.asarray(loss)  # bounds ALL the window's steps (chained)
        return (time.perf_counter() - t0) / timed_iters

    med, samples = _gated_samples(one_window, windows, spread_gate_pct)
    return med, state, samples


def _sample_fields(samples: list, used: int | None = None) -> dict:
    """The recorded evidence for one measurement: every window's
    avg s/step plus the spread (max-min as % of the median). When the
    spread gate extended the run, ``sample_spread_pct`` is the spread
    of the USED slice (the most recent ``used`` windows the median came
    from) and ``all_windows_spread_pct`` keeps the full-history spread
    so the extension is visible, never hidden."""
    tail = samples[-used:] if used else samples
    out = {
        "samples": [round(s, 6) for s in samples],
        "sample_spread_pct": round(_spread_pct(tail), 1),
    }
    if used and len(samples) > used:
        out["all_windows_spread_pct"] = round(_spread_pct(samples), 1)
        out["windows_extended_by_spread_gate"] = len(samples) - used
    return out


def run_bench(batch_size: int | None = None, timed_iters: int = 39,
              config: str | None = None, end_to_end_iters: int = 3,
              with_xla_flops: bool = True,
              with_multi_step: bool = True, windows: int = 3,
              with_dispatch_probe: bool = True) -> dict:
    import jax

    from tpu_ddp.models import VGG_CFG, get_model
    from tpu_ddp.models.resnet import RESNET_CFG
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.utils.config import TrainConfig
    from tpu_ddp.utils.timing import IterationTimer

    # Headline = the reference ladder's config; TPU_DDP_BENCH_CONFIG=
    # resnet50_imagenet runs the BASELINE.json stretch scale-up instead
    # (no reference number exists for it -> vs_baseline is null), and
    # transformer_lm dispatches to the LM tokens/sec bench.
    config = config or os.environ.get("TPU_DDP_BENCH_CONFIG",
                                      "vgg11_cifar10")
    if config == "transformer_lm":
        return run_lm_bench()
    cfg = TrainConfig.preset(config)
    if batch_size is None:
        batch_size = cfg.global_batch_size
    import jax.numpy as jnp
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      use_pallas_bn=cfg.pallas_bn,
                      compute_dtype=jnp.dtype(cfg.compute_dtype))
    # part3-equivalent (flagship) configuration: fused DP step, pinned to
    # exactly ONE chip so the per-chip metric stays honest on multi-chip
    # hosts (the pmean over a 1-slot axis degenerates gracefully).
    mesh = make_mesh(jax.devices()[:1])
    trainer = Trainer(model, cfg, strategy="fused", mesh=mesh)
    state = trainer.init_state()

    # Synthetic batches (bench must run with zero egress), staged on
    # device before the clock starts. Raw uint8 crosses host->device (4x
    # fewer bytes than host-normalized f32); normalization fuses into the
    # jitted step (Trainer._maybe_normalize).
    rng = np.random.default_rng(0)
    n_distinct = 4
    side = cfg.image_size
    host = [(rng.integers(0, 256, size=(batch_size, side, side, 3),
                          ).astype(np.uint8),
             rng.integers(0, cfg.num_classes, size=batch_size,
                          ).astype(np.int32)) for _ in range(n_distinct)]
    staged = [trainer.put_batch(x, y) for x, y in host]

    avg_s, state, samples = _chained_avg_s(trainer.train_step, state,
                                           staged, timed_iters, windows)

    # Multi-step dispatch (headline config only): one jitted lax.scan
    # over 16 full optimizer steps amortizes per-dispatch overhead — the
    # TPU-first way to run a dispatch-bound small model
    # (Trainer.build_multi_step; scan-of-k == k sequential steps,
    # tested). Round-4 verdict item 3: this chip-side protocol is the
    # HEADLINE now — the chained-dispatch number includes the host's
    # per-step dispatch; it stays recorded under
    # ``extra.chained_dispatch`` as the secondary.
    multi_step = None
    if with_multi_step and config == "vgg11_cifar10" and timed_iters >= 4:
        k = min(16, timed_iters)  # full 16 on real runs; small in tests
        multi = trainer.build_multi_step(k)
        reps = -(-k // len(host))
        xs = np.stack(([h[0] for h in host] * reps)[:k])
        ys = np.stack(([h[1] for h in host] * reps)[:k])
        staged_k = trainer.put_batches(xs, ys)
        state, losses = multi(state, *staged_k)
        np.asarray(losses)  # compile + warm
        state, losses = multi(state, *staged_k)
        np.asarray(losses)  # settle
        # Differenced windows: each window's wall time carries one fixed
        # readback on top of its chip time, so a single window size
        # would overstate the per-step time by readback/steps. Timing
        # a SMALL (n1 calls) and a BIG (n2 calls) window and
        # differencing cancels the fixed per-call readback —
        # per_step = (t_big - t_small) / ((n2-n1)*k) is chip time.
        n1, n2 = 2, 10

        def window(n_calls):
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(n_calls):
                state, losses = multi(state, *staged_k)
            np.asarray(losses)
            return time.perf_counter() - t0

        raw = []

        def one_pair():
            # A host hiccup in either window can make the difference
            # nonpositive — _gated_samples discards those (returns
            # None) instead of letting a corrupted sample reach the
            # headline median.
            t_small, t_big = window(n1), window(n2)
            raw.append({"t_small_s": round(t_small, 6),
                        "t_big_s": round(t_big, 6)})
            d = (t_big - t_small) / ((n2 - n1) * k)
            return d if d > 0 else None

        ms_windows = max(1, windows)
        try:
            per_step, ms_samples = _gated_samples(one_pair, ms_windows)
            multi_step = {
                "steps_per_call": k,
                "window_calls": [n1, n2],
                "avg_iter_s": round(per_step, 6),
                "images_per_sec": round(batch_size / per_step, 1),
                "window_times": raw,
                **_sample_fields(ms_samples, ms_windows),
            }
        except RuntimeError as e:
            # Every differenced sample corrupted: fall back to the
            # chained protocol as the headline rather than dying (the
            # discard is recorded in extra, never printed — stdout is
            # the driver's one-JSON-line channel).
            multi_step = {"error": f"RuntimeError: {e}",
                          "window_times": raw}

    # End-to-end per-iteration protocol (host->device transfer + step +
    # loss readback each iteration — the reference loop's exact shape,
    # part1/main.py:65-84): recorded for the record; it includes the
    # host round trip per step, hence not the headline.
    e2e = IterationTimer(first_iter=0, last_iter=end_to_end_iters - 1)
    for it in range(end_to_end_iters):
        e2e.start()
        xb, yb, wb = trainer.put_batch(*host[it % n_distinct])
        state, loss = trainer.train_step(state, xb, yb, wb)
        np.asarray(loss)
        e2e.stop(it)

    # Dispatch-depth probe (round 6): what the async pipeline
    # (tpu_ddp/train/pipeline.py) buys the STREAMING train_epoch loop —
    # steps/sec and host_gap_ms (host wall time idle inside forced
    # ``block_until_ready``) at depth 0 (the pre-round-6 synchronous
    # loop) vs the configured ``cfg.dispatch_depth``. Same protocol as
    # the committed artifact (scripts/host_gap.py — shared depth_sweep
    # helper), so the bench record and the artifact agree by
    # construction. Headline config only, like multi_step.
    dispatch_pipeline = None
    if (with_dispatch_probe and config == "vgg11_cifar10"
            and timed_iters >= 4):
        from tpu_ddp.train.pipeline import depth_sweep
        probe_depths = sorted({0, cfg.dispatch_depth or 2})
        try:
            probe, state = depth_sweep(trainer, state, host * 3,
                                       probe_depths, reps=1)
            # The headline cell is the deepest depth probed, which is
            # NOT cfg.dispatch_depth when the run is configured
            # synchronous (depth 0 still probes {0, 2} so the record
            # shows what the pipeline would buy) — probed_depth makes
            # the attribution explicit.
            probed = max(probe_depths)
            at_depth = probe[str(probed)]
            dispatch_pipeline = {
                "dispatch_depth": cfg.dispatch_depth,
                "probed_depth": probed,
                "host_gap_ms": at_depth["host_gap_ms"],
                "host_gap_ms_sync": probe["0"]["host_gap_ms"],
                "sweep": probe,
            }
        except Exception as e:  # noqa: BLE001 — probe must not kill it
            dispatch_pipeline = {"error": f"{type(e).__name__}: {e}"}

    # Analytic model FLOPs per forward step (tpu_ddp/utils/flops.py).
    from tpu_ddp.utils import flops as F
    if cfg.model in VGG_CFG:
        fwd = F.vgg_fwd_flops(VGG_CFG[cfg.model], side, batch_size,
                              cfg.num_classes)
    elif cfg.model in RESNET_CFG:
        fwd = F.resnet_fwd_flops(RESNET_CFG[cfg.model], side,
                                 batch_size, cfg.num_classes,
                                 small_inputs=side <= 64)
    elif hasattr(model, "num_patches"):
        fwd = F.vit_fwd_flops(model, batch_size)
    else:
        fwd = None  # unknown family: XLA cost analysis only
    # Headline value (round-4 verdict item 3): the chip-side multi_step
    # per-step time when measured; the chained number is the secondary.
    promoted = multi_step is not None and "error" not in multi_step
    best_avg = (multi_step["avg_iter_s"] if promoted else avg_s)
    # xla cost analysis forces a fresh AOT compile — worth it once per
    # config as the cross-check, skipped for repeat runs (batch sweep).
    mfu = _mfu_block(
        fwd, best_avg,
        trainer._train_step if with_xla_flops else None,
        (state.params, state.opt_state, *staged[0])
        if with_xla_flops else None)

    imgs_per_sec = batch_size / best_avg
    headline = config == "vgg11_cifar10"
    chained = {
        "avg_iter_s": round(avg_s, 6),
        "images_per_sec": round(batch_size / avg_s, 1),
        **_sample_fields(samples, windows),
    }
    return {
        "metric": ("cifar10_vgg11_images_per_sec_per_chip" if headline
                   else f"{cfg.dataset}_{cfg.model.lower()}"
                        "_images_per_sec_per_chip"),
        "value": round(imgs_per_sec, 1),
        "unit": "images/sec",
        "vs_baseline": round(imgs_per_sec / 386.0, 2) if headline else None,
        "extra": {
            "avg_iter_s": round(best_avg, 6),
            **({"multi_step": multi_step} if multi_step else {}),
            **({"chained_dispatch": chained} if promoted else chained),
            "end_to_end_iter_s": round(e2e.average_s, 6),
            "dispatch_depth": cfg.dispatch_depth,
            # Active gradient wire format (parallel/compress.py) — the
            # record must say which compressor produced its numbers,
            # same contract as the dispatch_pipeline probe below.
            "grad_compress": trainer.compressor.describe(),
            # Active memory policy (tpu_ddp/memory/) — the effective
            # per-model value after Trainer imprints the config, so an
            # env/flag override shows up in the record.
            "remat": getattr(trainer.model, "remat_policy",
                             getattr(trainer.model, "remat", "none")),
            "act_dtype": getattr(trainer.model, "act_dtype", "compute"),
            **({"dispatch_pipeline": dispatch_pipeline}
               if dispatch_pipeline else {}),
            "batch_size": batch_size,
            "timed_iters": timed_iters,
            "timing_protocol": (
                "multi-step scan dispatch (16 chip-side optimizer steps "
                "per call; headline since round 5 — differenced "
                "windows); chained-dispatch secondary under "
                "extra.chained_dispatch" if promoted else
                "chained dispatch, single final readback "
                "(see bench.py docstring)"),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            **mfu,
            "baseline": "part3 torch-DDP, 4 CPU nodes, ~386 img/s aggregate "
                        "(BASELINE.md)",
        },
    }


def run_lm_bench(batch_size: int = 8, seq_len: int = 2048,
                 timed_iters: int = 20, use_flash: bool = True,
                 with_xla_flops: bool = True,
                 model_name: str = "TransformerLM-small",
                 with_decode: bool = True,
                 model_overrides: dict | None = None,
                 windows: int = 3, trainer_overrides: dict | None = None,
                 ) -> dict:
    """Transformer-LM training throughput (tokens/sec) on one chip.
    ``use_flash`` selects the Pallas flash-attention kernel
    (tpu_ddp/ops/pallas) vs the jnp attention path — benched both ways by
    ``main`` so the kernel's win is a recorded number. ``model_name``
    picks the preset: the small config mirrors round 1/2's numbers; the
    MXU-saturating TransformerLM-large is the MFU-headline config
    (round-2 verdict: a 4-layer/512-wide model cannot fill the MXU).
    Not the headline metric (the reference has no LM workload)."""
    import jax

    from tpu_ddp.models import make_transformer
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer, make_lm_batch

    model = make_transformer(model_name, max_seq_len=seq_len,
                             use_flash=use_flash,
                             **(model_overrides or {}))
    trainer = LMTrainer(model, make_mesh(jax.devices()[:1]),
                        **(trainer_overrides or {}))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model.vocab_size,
                          size=(batch_size, seq_len + 1))
    staged = [trainer.put_batch(*make_lm_batch(tokens))]

    avg_s, state, samples = _chained_avg_s(trainer.train_step, state,
                                           staged, timed_iters, windows)

    from tpu_ddp.utils import flops as F
    fwd = F.transformer_fwd_flops(model, batch_size, seq_len)
    mfu = _mfu_block(
        fwd, avg_s,
        trainer._train_step if with_xla_flops else None,
        (state.params, state.opt_state, *staged[0],
         *trainer._extra_args(state)) if with_xla_flops else None)

    # KV-cache decode throughput (models/generate.py): the whole decode
    # loop is ONE jitted lax.scan dispatch, so the per-call dispatch
    # amortizes over all generated tokens. Recorded per flash config
    # that asks for it (main(): the small LM and TransformerLM-large;
    # the decode path itself is kernel-independent).
    decode = None
    if use_flash and with_decode:
        from tpu_ddp.models import generate

        def run_decode():
            # state.params live replicated on the 1-chip mesh — usable
            # directly, with no host round-trip.
            params = state.params
            b, prompt_len, new_tokens = 8, 128, 256
            prompt = rng.integers(0, model.vocab_size,
                                  size=(b, prompt_len))
            out = generate(model, params, prompt,
                           max_new_tokens=new_tokens)
            np.asarray(out)  # compile+warm
            t0 = time.perf_counter()
            for _ in range(3):
                out = generate(model, params, prompt,
                               max_new_tokens=new_tokens)
            np.asarray(out)
            dt = (time.perf_counter() - t0) / 3
            ms_per_step = dt / new_tokens * 1e3
            # HBM-bandwidth accounting (round-4 verdict item 4): decode
            # is memory-bound, so the honest efficiency yardstick is
            # achieved bytes/s vs the chip's HBM peak, not MFU. Per
            # token-step the chip must read EVERY parameter and both
            # K/V caches — the caches are preallocated to prompt+new
            # and the masked attention einsum contracts over the FULL
            # buffer every step (models/generate.py:_attend_cached,
            # static shapes), so the read length is total_len, not the
            # live length. Params are counted at COMPUTE dtype (bf16):
            # the f32->bf16 casts are loop-invariant, so XLA hoists
            # them out of the decode scan and the steady-state reads
            # are the bf16 copies — counting f32 storage produced an
            # impossible >1.0 utilization (measured round 5). The
            # EMBEDDING table is the exception both ways: decode only
            # GATHERS batch-many rows per step
            # (models/transformer.py: params["embed"][tokens]), so the
            # full (V, dm) table is excluded and b rows are charged
            # instead (the head matmul DOES read its full (dm, V)).
            # The measured dt also contains the one prefill per call
            # (charged as ~prompt_len/new_tokens extra full-param
            # passes is <1% here; noted, not modeled).
            c_item = np.dtype(model.compute_dtype).itemsize
            param_bytes = (
                sum(int(p.size) * c_item
                    for p in jax.tree.leaves(params))
                - model.vocab_size * model.d_model * c_item  # embed
                + b * model.d_model * c_item)  # gathered rows
            total_len = prompt_len + new_tokens
            kv_bytes = (model.num_layers * 2 * b * total_len
                        * model.kv_heads * model.head_dim * c_item)
            bytes_per_step = param_bytes + kv_bytes
            achieved = bytes_per_step / (ms_per_step * 1e-3)
            from tpu_ddp.utils import flops as F
            bw_gbps, bw_src = F.device_hbm_gbps(jax.devices()[0])
            return {"batch": b, "prompt_len": prompt_len,
                    "new_tokens": new_tokens,
                    "tokens_per_sec": round(b * new_tokens / dt, 1),
                    "ms_per_token_step": round(ms_per_step, 3),
                    "hbm_util": {
                        "param_bytes": param_bytes,
                        "kv_cache_bytes_per_step": kv_bytes,
                        "bytes_per_token_step": bytes_per_step,
                        "achieved_gbps": round(achieved / 1e9, 1),
                        "peak_gbps": bw_gbps,
                        "peak_source": bw_src,
                        "utilization": (
                            round(achieved / (bw_gbps * 1e9), 4)
                            if bw_gbps else None),
                    }}

        decode = _sub(run_decode)

    toks_per_sec = batch_size * seq_len / avg_s
    return {
        "metric": "transformer_lm_tokens_per_sec_per_chip",
        "value": round(toks_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": None,
        "extra": {
            "avg_iter_s": round(avg_s, 6),
            **_sample_fields(samples, windows),
            "batch_size": batch_size,
            "seq_len": seq_len,
            "timed_iters": timed_iters,
            "model": model.name,
            "flash_attention": use_flash,
            "remat": model.remat_policy,
            "act_dtype": model.act_dtype,
            **({"decode": decode} if decode else {}),
            "platform": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            **mfu,
            "baseline": "no reference LM workload exists (SURVEY.md §5)",
        },
    }


def run_collectives_bench(mb: float = 16.0, iters: int = 10) -> dict:
    """ICI collective microbench over ALL attached devices (VERDICT r1
    weak #7: comm regressions need a recorded baseline). With one chip
    there is no ICI to measure — recorded as skipped, not faked."""
    import jax

    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.utils.collectives import bench_collectives

    devices = jax.devices()
    if len(devices) < 2:
        return {"skipped": f"1 device attached ({devices[0].device_kind});"
                           " ICI collectives need >= 2"}
    mesh = make_mesh(devices)
    return {"devices": len(devices), "payload_mib": mb,
            "results": bench_collectives(mesh, mb=mb, iters=iters)}


def run_autotune_probe(families=("vgg11_cifar10",
                                 "resnet50_imagenet")) -> dict:
    """Tuned-vs-default steps/sec per bench family (tpu_ddp/tune/) —
    the tuner paying rent in the headline artifact. Cache-free by
    design (``tune.tuned_vs_default``): the probe measures what a fresh
    search finds on THIS chip today, not what an old entry says.

    The search's regression guard means ``tuned >= default`` for every
    family by construction (equal when the defaults already win —
    expected for vgg11, whose defaults were hand-tuned over rounds 5-7;
    the interesting number is resnet50, stuck at 0.259 MFU hand-tuned).
    """
    from tpu_ddp import tune

    iters = int(os.environ.get("TPU_DDP_TUNE_ITERS", "8"))
    out = {}
    for family in families:
        out[family] = _sub(tune.tuned_vs_default, family,
                           n_batches=iters)
        cell = out[family]
        if "error" not in cell \
                and cell["default_steps_per_sec"] is not None \
                and cell["tuned_steps_per_sec"] is not None:
            cell["speedup"] = round(cell["tuned_steps_per_sec"]
                                    / cell["default_steps_per_sec"], 3)
    return out


def run_remat_probe(config: str = "resnet50_imagenet",
                    policies=("none", "blocks", "conv_stages")) -> dict:
    """Memory-policy deltas on the big-activation cell (tpu_ddp/memory/):
    compiled bytes-accessed + temp bytes (and step time, on TPU) for
    remat=none vs each non-duplicate conv policy, through the SAME cell
    protocol as the committed sweep (scripts/remat_sweep.py) — so the
    bench record and experiments/remat_sweep.json agree by construction
    (the host_gap/depth_sweep precedent). ``best`` names the policy
    with the largest bytes-accessed cut that does not regress the
    measured step (untimed on CPU: best-by-bytes alone, flagged)."""
    from scripts.remat_sweep import measure_conv_cell

    bs = int(os.environ.get("TPU_DDP_RESNET_BATCH", "512"))
    cells = {p: _sub(measure_conv_cell, config, bs, p) for p in policies}
    out: dict = {"batch": bs, "cells": cells}
    base = cells.get("none", {})
    xb0 = base.get("xla_bytes_accessed")
    tb0 = base.get("temp_bytes")
    t0 = base.get("measured_step_s")
    best, best_cut = None, 0.0
    for p, cell in cells.items():
        if p == "none" or "error" in cell:
            continue
        xb, tb = cell.get("xla_bytes_accessed"), cell.get("temp_bytes")
        if xb0 and xb:
            cell["bytes_accessed_cut_pct"] = round(
                100.0 * (xb0 - xb) / xb0, 1)
        if tb0 and tb:
            cell["temp_bytes_cut_pct"] = round(
                100.0 * (tb0 - tb) / tb0, 1)
        t = cell.get("measured_step_s")
        if t0 and t:
            cell["step_time_vs_none"] = round(t / t0, 3)
        cut = cell.get("bytes_accessed_cut_pct", 0.0)
        timed = t0 is not None and t is not None
        ok = (t <= 1.02 * t0) if timed else True
        if ok and cut > best_cut:
            best, best_cut = p, cut
    out["best"] = best
    out["timed"] = t0 is not None
    return out


def run_overlap_probe(config: str = "resnet50_imagenet") -> dict:
    """Overlapped-collectives probe (tpu_ddp/parallel/overlap.py) on the
    MFU-plateau cell: the committed fused rung vs the bucketized path at
    DDP's 25 MB default, through the committed sweep's own cell protocol
    (scripts/overlap_sweep.py — the remat-probe precedent). Records the
    compiled-HLO overlap verdict per cell (``hlo_comm.overlap_report``;
    the bucketized cell must pass ``assert_overlap``'s rule) and, on
    TPU, the steps/sec delta — the number that moves the resnet50 MFU
    off its 0.2588 all-reduce-bound plateau."""
    from scripts.overlap_sweep import measure_overlap_cell

    bs = int(os.environ.get("TPU_DDP_RESNET_BATCH", "512"))
    baseline = _sub(measure_overlap_cell, config, bs, "fused", None)
    overlapped = _sub(measure_overlap_cell, config, bs, "fused", 25)
    out = {"baseline": baseline, "overlapped": overlapped}
    rep = overlapped.get("overlap_report")
    if rep:
        # the bench artifact records the verdict; tests enforce it
        out["assert_overlap_passes"] = bool(rep.get("overlapped"))
    t0 = baseline.get("measured_step_s")
    t1 = overlapped.get("measured_step_s")
    if t0 and t1:
        out["speedup"] = round(t0 / t1, 3)
    out["timed"] = t0 is not None and t1 is not None
    return out


def run_serve_probe(n_requests: int = 24) -> dict:
    """Serving probe (tpu_ddp/serve/): TTFT + goodput for continuous
    vs static batching at 1.5x this host's measured saturation rate,
    through the committed sweep's own cell protocol
    (scripts/serve_sweep.py — the remat/overlap-probe precedent). The
    recorded claim is the ORDERING (continuous >= static on goodput
    under oversubscription — the serve subsystem's reason to exist);
    absolute tokens/sec are host-relative scheduling numbers, valid on
    CPU because the probe model is tiny by design."""
    from scripts.serve_sweep import build_engine
    from tpu_ddp.serve import calibrate_rate, make_workload, run_load

    specs = make_workload(n_requests, vocab_size=1024, seed=0,
                          prompt_len=(4, 17), max_new=(4, 25))
    # Warm the jitted steps (memoized per cache geometry) outside every
    # timed window, then derive the fixed SLO from an unloaded TTFT.
    warm = build_engine()
    for sp in specs[:3]:
        warm.submit(sp.prompt, sp.max_new_tokens)
    warm.run()
    probe = build_engine()
    h = probe.submit(specs[0].prompt, specs[0].max_new_tokens)
    probe.run()
    slo_ms = max(50.0, 10.0 * h.ttft_s * 1e3)
    rate = 1.5 * calibrate_rate(build_engine, specs)
    out = {"slo_ttft_ms": round(slo_ms, 3),
           "rate_rps": round(rate, 3)}
    for mode in ("continuous", "static"):
        out[mode] = _sub(run_load, build_engine(mode), specs, rate,
                         seed=1, slo_ttft_ms=slo_ms)
    cg = out["continuous"].get("goodput_tokens_per_sec")
    sg = out["static"].get("goodput_tokens_per_sec")
    if cg is not None and sg is not None:
        out["continuous_beats_static"] = bool(cg > sg)
        out["goodput_ratio"] = round(cg / sg, 3) if sg else None
    return out


def run_long_context_probe() -> dict:
    """Long-context probe (serve/long_context.py, DESIGN.md §27): TTFT
    per prompt token on a 512-token prompt whose KV footprint is 8x
    the hot tier, tiered (int8 cold pages + host spill) vs the
    fully-resident single pool, plus bitwise mid-size decode parity
    through the lossless bf16 cold codec. The recorded claims are the
    RATIO (near 1.0: the tier traffic hides behind prefill compute)
    and the parity bit; the enforced <= 1.2x gate lives in
    scripts/long_context_sweep.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_ddp.models.transformer import make_transformer
    from tpu_ddp.serve import ServeEngine

    model = make_transformer("TransformerLM-tiny", max_seq_len=1024,
                             num_layers=4, d_model=256, d_ff=1024,
                             compute_dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    prompt = np.random.default_rng(5).integers(
        0, model.vocab_size, size=512).astype(np.int32)

    def ttft(**knobs):
        best = None
        for _ in range(3):
            eng = ServeEngine(model, params, num_slots=1,
                              block_size=32, prefill_chunk=64, **knobs)
            stamp: list = []
            eng.submit(prompt, 4,
                       on_token=lambda t: stamp.append(
                           time.perf_counter()) if not stamp else None)
            t0 = time.perf_counter()
            eng.run()
            dt = stamp[0] - t0
            best = dt if best is None else min(best, dt)
        return best

    res = ttft()
    trd = ttft(kv_tiers=3, kv_cold_dtype="int8", hbm_blocks=3,
               cold_blocks=33)
    out = {
        "prompt_tokens": 512,
        "hot_capacity_tokens": 64,
        "oversubscription_x": 8.0,
        "resident_ttft_per_token_us": round(res / 512 * 1e6, 2),
        "tiered_ttft_per_token_us": round(trd / 512 * 1e6, 2),
        "ttft_per_token_ratio": round(trd / res, 3),
    }

    # Mid-size bitwise parity through the lossless bf16 cold tier.
    mmodel = make_transformer("TransformerLM-tiny", max_seq_len=64,
                              compute_dtype=jnp.float32)
    mparams = mmodel.init(jax.random.key(1))
    geom = dict(num_slots=4, block_size=8, prefill_chunk=8,
                cache_dtype="bf16")

    def streams(**knobs):
        eng = ServeEngine(mmodel, mparams, **geom, **knobs)
        hs = [eng.submit(np.random.default_rng(40 + i).integers(
            0, 1024, size=L).astype(np.int32), n)
            for i, (L, n) in enumerate([(20, 6), (11, 8), (9, 5)])]
        eng.run()
        return [list(h.tokens) for h in hs]

    out["midsize_bitwise_parity"] = bool(
        streams() == streams(kv_tiers=3, kv_cold_dtype="bf16",
                             hbm_blocks=6, cold_blocks=33))
    return out


def run_fleet_probe(n_requests: int = 24) -> dict:
    """Fleet probe (tpu_ddp/fleet/): disaggregated prefill/decode with
    the refcounted prefix cache vs the round-12 single engine at 1.5x
    the single engine's measured saturation, EQUAL simulated hardware
    (single-engine block budget = disagg decode+prefill pools
    combined), on a shared-system-prompt workload. The recorded claim
    is the ORDERING (``fleet_beats_single``: disagg+prefix wins p99
    TTFT under oversubscription — the fleet subsystem's reason to
    exist); absolute ms are host-relative, valid on CPU because
    scheduling, not matmul, dominates the tiny probe model."""
    from scripts.serve_sweep import build_engine
    from tpu_ddp.serve import (calibrate_rate,
                               make_shared_prefix_workload, run_load)

    specs = make_shared_prefix_workload(
        n_requests, vocab_size=1024, seed=0, prefix_len=48,
        tail_len=(2, 9), max_new=(2, 7))
    geom = dict(serve_prefill_chunk=16)
    bps = 64 // 16
    single_blocks = (8 * bps + 1) + (2 * bps + 1)

    def build_single():
        return build_engine(num_blocks=single_blocks, **geom)

    def build_fleet():
        return build_engine(fleet_roles="disagg", prefix_cache=True,
                            **geom)

    for b in (build_single, build_fleet):  # warm outside every window
        e = b()
        for sp in specs[:3]:
            e.submit(sp.prompt, sp.max_new_tokens)
        e.run()
    probe = build_single()
    h = probe.submit(specs[0].prompt, specs[0].max_new_tokens)
    probe.run()
    slo_ms = max(50.0, 10.0 * h.ttft_s * 1e3)
    rate = 1.5 * calibrate_rate(build_single, specs)
    out = {"slo_ttft_ms": round(slo_ms, 3),
           "rate_rps": round(rate, 3),
           "single_num_blocks": single_blocks}
    fleet_eng = build_fleet()
    out["single"] = _sub(run_load, build_single(), specs, rate,
                         seed=1, slo_ttft_ms=slo_ms)
    out["disagg_prefix"] = _sub(run_load, fleet_eng, specs, rate,
                                seed=1, slo_ttft_ms=slo_ms)
    if "error" not in out["disagg_prefix"]:
        out["disagg_prefix"]["edge"] = fleet_eng.edge.stats()
        out["disagg_prefix"]["prefix"] = fleet_eng.prefix.stats()
    fp = out["disagg_prefix"].get("ttft_p99_ms")
    sp = out["single"].get("ttft_p99_ms")
    if fp is not None and sp is not None:
        out["fleet_beats_single"] = bool(fp < sp)
        out["ttft_p99_ratio"] = round(sp / fp, 3) if fp else None
    return out


def run_fleet_resilience_probe(n_requests: int = 24) -> dict:
    """Fleet-resilience probe (tpu_ddp/fleet/resilience.py, DESIGN.md
    §23): goodput of a 3-replica routed fleet with 1 replica chaos-
    crashed mid-load vs the same fleet healthy, identical workload and
    Poisson rate. The recorded claim is ``degraded_goodput_ratio``
    >= 0.55 — losing a third of the fleet must cost roughly a third of
    the goodput (requests migrate and finish), not all of it — plus
    ``replica_readmitted``: the backoff probe restores the crashed
    replica once its one-shot fault has fired. Absolute tokens/sec are
    host-relative; the ratio and the re-admission are the claims."""
    import os
    import time as _time

    from scripts.serve_sweep import build_engine
    from tpu_ddp.fleet import Router
    from tpu_ddp.serve import calibrate_rate, make_workload, run_load

    specs = make_workload(n_requests, vocab_size=1024, seed=0,
                          prompt_len=(4, 17), max_new=(4, 17))

    def build_fleet():
        return Router([build_engine() for _ in range(3)],
                      probe_backoff_ms=100.0)

    e = build_engine()                      # warm outside every window
    for sp in specs[:3]:
        e.submit(sp.prompt, sp.max_new_tokens)
    e.run()
    # Rate sized to ONE replica's saturation: the 3-replica fleet is
    # comfortably provisioned, so the healthy run clears its SLO and
    # the crashed run's deficit measures resilience, not overload.
    rate = calibrate_rate(build_engine, specs)
    probe = build_engine()
    h = probe.submit(specs[0].prompt, specs[0].max_new_tokens)
    probe.run()
    slo_ms = max(100.0, 20.0 * h.ttft_s * 1e3)
    out = {"slo_ttft_ms": round(slo_ms, 3), "rate_rps": round(rate, 3),
           "n_replicas": 3}
    out["healthy"] = _sub(run_load, build_fleet(), specs, rate,
                          seed=1, slo_ttft_ms=slo_ms)
    os.environ["TPU_DDP_CHAOS_FAULTS"] = "replica-crash@6:rank=0"
    try:
        import warnings as _warnings
        with _warnings.catch_warnings():
            _warnings.simplefilter("ignore")
            crashed_fleet = build_fleet()
            out["crashed"] = _sub(run_load, crashed_fleet, specs, rate,
                                  seed=1, slo_ttft_ms=slo_ms)
            # Drive the probe loop until the backoff re-admits the
            # one-shot-crashed replica.
            deadline = _time.monotonic() + 5.0
            while (crashed_fleet.readmitted == 0
                   and _time.monotonic() < deadline):
                crashed_fleet.step()
                _time.sleep(0.01)
    finally:
        del os.environ["TPU_DDP_CHAOS_FAULTS"]
    out["crashed"]["router"] = {
        k: crashed_fleet.stats()[k]
        for k in ("failovers", "readmitted", "migrated", "retried",
                  "shed")}
    out["replica_readmitted"] = bool(crashed_fleet.readmitted)
    hg = out["healthy"].get("goodput_tokens_per_sec")
    cg = out["crashed"].get("goodput_tokens_per_sec")
    if hg and cg is not None:
        out["degraded_goodput_ratio"] = round(cg / hg, 3)
        out["resilient"] = bool(cg / hg >= 0.55
                                and out["replica_readmitted"])
    return out


def run_fleet_autoscale_probe(n_boots: int = 5) -> dict:
    """Autoscale reaction-time probe (tpu_ddp/fleet/autoscale.py,
    DESIGN.md §25): how fast a scale-up decision becomes a SERVING
    replica. Boot-from-push (factory engine + ``Publisher.bootstrap``
    full push, the Autoscaler's path) vs checkpoint restart
    (``ServeEngine.from_checkpoint``), medians over ``n_boots`` boots
    on this chip. The recorded claims are ``push_faster`` — the push
    path must beat the restart — and the structural half: a pushed
    boot joins at the fleet's CURRENT published version while the
    restart serves the stale on-disk save and would still need a
    catch-up push before it matched the fleet."""
    import shutil
    import statistics
    import tempfile
    import time as _time

    import jax

    from scripts.serve_sweep import build_engine
    from tpu_ddp.publish.publisher import Publisher
    from tpu_ddp.publish.subscriber import Subscriber, attach
    from tpu_ddp.serve import ServeEngine
    from tpu_ddp.utils.checkpoint import save_checkpoint

    seed_eng = build_engine()
    model, params = seed_eng.model, seed_eng.params
    geom = dict(num_slots=seed_eng.num_slots,
                block_size=seed_eng.block_size,
                prefill_chunk=seed_eng.prefill_chunk)
    current = jax.tree.map(lambda x: x + 0.01, params)

    ckpt = tempfile.mkdtemp(prefix="bench-autoscale-ckpt-")
    try:
        # The on-disk artifact is a train-time save of the ORIGINAL
        # params; the fleet has since moved to `current` via the
        # publisher — exactly the gap a restarted replica wakes into.
        save_checkpoint(ckpt, {"params": params}, 0)
        pub = Publisher(publish_every=1, wire="none", bucket_mb=0.25)
        seed_sub = attach(pub, seed_eng, name="seed")[0]
        seed_eng.subscriber = seed_sub
        pub.publish(params=current, step=1)
        while seed_sub.lag:
            seed_eng.step()

        def push_boot():
            t0 = _time.perf_counter()
            eng = ServeEngine(model, params, **geom)
            sub = Subscriber(eng, name="boot")
            eng.subscriber = sub
            pub.connect(sub)
            pub.bootstrap(sub)
            while sub.lag:
                eng.step()
            dt = _time.perf_counter() - t0
            pub.subscribers.remove(sub)
            return dt, eng

        def ckpt_boot():
            t0 = _time.perf_counter()
            eng = ServeEngine.from_checkpoint(model, ckpt, **geom)
            return _time.perf_counter() - t0, eng

        push_boot(), ckpt_boot()        # warm both paths once
        push_ts, push_engs = zip(*(push_boot()
                                   for _ in range(n_boots)))
        ckpt_ts, ckpt_engs = zip(*(ckpt_boot()
                                   for _ in range(n_boots)))
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    push_med = statistics.median(push_ts)
    ckpt_med = statistics.median(ckpt_ts)
    return {
        "push_boot_s_median": round(push_med, 5),
        "ckpt_restart_s_median": round(ckpt_med, 5),
        "push_boot_s": sorted(round(t, 5) for t in push_ts),
        "ckpt_restart_s": sorted(round(t, 5) for t in ckpt_ts),
        "push_faster": bool(push_med < ckpt_med),
        "push_joins_at_current_version": bool(
            all(e.param_version == pub.version for e in push_engs)),
        "ckpt_restart_is_stale": bool(
            all(e.param_version == 0 for e in ckpt_engs)),
        "publisher_version": pub.version,
        "bootstraps": pub.bootstraps,
    }


def run_graph_audit_probe() -> dict:
    """Static graph audit (tpu_ddp/analysis/) on THIS backend's
    compiled programs, through the committed sweep's own cell protocol
    (scripts/graph_audit.py). The CPU tier already pins the verdicts;
    what the chip adds is the lowering the CPU never sees — TPU
    schedules emit async ``-start``/``-done`` collective pairs, so the
    fingerprints recorded here exercise the pair-normalized counting
    on real hardware and the donation/precision checks run against
    the exact executables bench times."""
    from scripts.graph_audit import audit_train_cell

    out: dict = {"cells": {}}
    for rung, kw in (("fused", {}), ("fused", {"grad_compress": "bf16"})):
        cell = _sub(audit_train_cell, rung, **kw)
        key = rung + ("+" + kw["grad_compress"] if kw else "")
        out["cells"][key] = {
            k: cell.get(k) for k in ("n_collectives", "findings",
                                     "wire", "error")
            if k in cell}
    out["clean"] = all(not c.get("findings") and "error" not in c
                       for c in out["cells"].values())
    return out


def run_moe_probe(steps: int = 4) -> dict:
    """MoE routing-health probe (tpu_ddp/parallel/moe.py): train the
    tiny MoE preset a few steps on one chip and record the counters the
    training metrics line carries — dropped-token fraction, per-expert
    load histogram and imbalance (max load x E; 1.0 = balanced) per
    routed layer, via LMTrainer.route_stats on the final weights — plus
    first/last loss, so a collapsed router (imbalance -> E) is visible
    next to its loss signature. The enforced MoE-vs-dense step-time and
    wire-bytes gates live in scripts/moe_sweep.py."""
    import jax

    from tpu_ddp.models import make_transformer
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import (LMTrainer, format_route_stats,
                                  make_lm_batch)

    model = make_transformer("TransformerLM-moe-tiny", max_seq_len=64)
    trainer = LMTrainer(model, make_mesh(jax.devices()[:1]))
    state = trainer.init_state()
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model.vocab_size, size=(8, 65))
    batch = trainer.put_batch(*make_lm_batch(tokens))
    losses = []
    for _ in range(steps):
        state, loss = trainer.train_step(state, *batch)
        losses.append(float(np.mean(np.asarray(loss))))
    stats = trainer.route_stats(state, tokens[:, :-1])
    layers = [{
        "dropped_frac": round(float(s["dropped_frac"]), 4),
        "imbalance": round(float(s["imbalance"]), 3),
        "expert_load": [round(float(x), 4)
                        for x in np.asarray(s["expert_load"])],
    } for s in stats]
    return {"model": model.name, "experts": model.moe_experts,
            "top_k": model.moe_top_k,
            "capacity_factor": model.moe_capacity_factor,
            "loss_first": round(losses[0], 4),
            "loss_last": round(losses[-1], 4),
            "layers": layers,
            "metrics_line": format_route_stats(stats).strip()}


def _sub(fn, *args, **kwargs) -> dict:
    """Run one sub-benchmark. A failure is recorded in its cell so the
    remaining cells still run and the headline line still prints; the
    traceback goes to stderr and :func:`cli` exits non-zero for it."""
    try:
        return fn(*args, **kwargs)
    except Exception as e:  # noqa: BLE001 — boundary: the run continues
        traceback.print_exc()
        return {"error": f"{type(e).__name__}: {e}"}


def failed_cells(result, path: str = "") -> list:
    """``"path: error"`` for every cell of a result tree that recorded
    an error (:func:`_sub`, or a cell's own ``{"error": ...}``)."""
    if isinstance(result, (list, tuple)):
        items = enumerate(result)
    elif isinstance(result, dict):
        items = result.items()
    else:
        return []
    out = []
    if isinstance(result, dict) and result.get("error"):
        out.append(f"{path or '<top>'}: {result['error']}")
    for key, value in items:
        out += failed_cells(value, f"{path}.{key}" if path else str(key))
    return out


def main() -> dict:
    # Headline pinned to the reference ladder's config — explicit, so
    # TPU_DDP_BENCH_CONFIG (a single-config debugging hook for run_bench)
    # can never relabel the headline or double-run a sub-benchmark.
    # 5 windows (vs 3 elsewhere): this is the one dispatch-bound cell,
    # so its median needs the most protection against a host hiccup
    # landing in a window.
    result = run_bench(config="vgg11_cifar10", windows=5)

    extra = result["extra"]
    # Throughput vs batch size: the headline batch (the reference's
    # global 256) leaves a ~6 ms step dispatch-bound on this chip; the
    # sweep runs until the MFU plateau (round-2 verdict: 2048 stopped
    # while MFU was still rising).
    sweep = {}
    for bs in (1024, 2048, 4096, 8192, 16384):
        r = _sub(run_bench, batch_size=bs, timed_iters=10,
                 config="vgg11_cifar10", end_to_end_iters=1,
                 with_xla_flops=False, with_multi_step=False,
                 with_dispatch_probe=False)
        sweep[str(bs)] = (
            {"images_per_sec": r["value"], "mfu": r["extra"]["mfu"]}
            if "error" not in r else r)
    extra["batch_sweep"] = sweep

    def _resnet():
        # Parse the env override INSIDE the _sub-guarded call so a junk
        # value becomes a recorded error, not a lost headline line.
        # Default 512 = the measured MFU plateau (see batch_sweep below;
        # round-3 verdict item 1a — 128 was far from saturation).
        bs = int(os.environ.get("TPU_DDP_RESNET_BATCH", "512"))
        return run_bench(batch_size=bs, timed_iters=10,
                         config="resnet50_imagenet", end_to_end_iters=1)

    extra["configs"] = {"resnet50_imagenet": _sub(_resnet)}
    # ResNet-50 batch sweep to ITS plateau (round-3 verdict item 1a):
    # same machinery as the VGG sweep; an OOM cell records as an error.
    rsweep = {}
    for bs in (128, 256, 512, 1024):
        r = _sub(run_bench, batch_size=bs, timed_iters=6,
                 config="resnet50_imagenet", end_to_end_iters=1,
                 with_xla_flops=False, with_multi_step=False)
        rsweep[str(bs)] = (
            {"images_per_sec": r["value"], "mfu": r["extra"]["mfu"]}
            if "error" not in r else r)
    cfg_r = extra["configs"]["resnet50_imagenet"]
    if "error" not in cfg_r:
        cfg_r["extra"]["batch_sweep"] = rsweep
    else:
        extra["configs"]["resnet50_imagenet"] = {
            **cfg_r, "batch_sweep": rsweep}
    # The MFU-headline LM config (round-3 verdict item 1b): ~740M params,
    # every matmul K,N >= 2048, head_dim 128. remat off — it fits at
    # batch 4 microbatches, and the recomputed forward would burn 25% of
    # counted MFU (MFU counts 3x fwd; remat executes 4x). Round-4
    # tuning, measured on the v5e (median-of-3 windows, ~0.3% spread):
    # flash tiles fwd 512/1024 + bwd 512/512 (now the kernel defaults)
    # took batch 4 from 0.5145 -> 0.5857; grad_accum=4 at batch 16
    # (microbatch 4, 32k tokens/optimizer step) adds the update
    # amortization -> 0.594-0.596. Non-flash attention fails to compile
    # at this scale (the (B,H,L,L) score tensor); remat variants sit
    # ~0.40; vocab_chunk measured worse (0.471).
    # Round-5 re-tune: raising the accumulated batch lifts the MFU
    # headline further (update amortization + steadier microbatch-4
    # stream): 16x4 -> 0.598, 32x8 -> 0.609, 64x16 -> 0.6175,
    # 128x32 -> 0.622 (measured ladder below; microbatch 8 variants
    # fail to compile at this scale). 64x16 is the recorded headline
    # cell (128x32's ~10 s optimizer step makes its windows too coarse
    # for the default run); the ladder cells pin the trend.
    extra["configs"]["transformer_lm_large"] = _sub(
        run_lm_bench, model_name="TransformerLM-large", batch_size=64,
        timed_iters=3, with_decode=True,
        model_overrides={"remat": "none"},
        trainer_overrides={"grad_accum": 16})
    large = extra["configs"]["transformer_lm_large"]
    if "error" not in large:
        ladder = {}
        for bs, ga in ((16, 4), (32, 8), (128, 32)):
            r = _sub(run_lm_bench, model_name="TransformerLM-large",
                     batch_size=bs, timed_iters=2, with_xla_flops=False,
                     with_decode=False,
                     model_overrides={"remat": "none"},
                     trainer_overrides={"grad_accum": ga})
            ladder[f"{bs}x{ga}"] = (
                {"batch": bs, "grad_accum": ga,
                 "tokens_per_sec": r["value"],
                 "mfu": r["extra"]["mfu"]}
                if "error" not in r else r)
        large["extra"]["batch_sweep"] = ladder
    # Long-context training (TransformerLM-large, seq 8192, flash): the
    # regime where the O(L*D)-memory kernel is the enabling piece — the
    # jnp attention path cannot even compile the O(L^2) score tensor
    # here. batch 1, remat off (remat OOMs at this length; the no-remat
    # step fits). Measured v5e round 4: ~18.6k tok/s, 0.607 MFU with
    # the tuned tiles (was 0.4165 at the old 256/512+256/256 tiles) —
    # the seq-8192 rows amortize the kernel's per-grid-step scratch
    # best, so this cell now leads the MFU table.
    extra["configs"]["transformer_lm_long"] = _sub(
        run_lm_bench, model_name="TransformerLM-large", batch_size=1,
        seq_len=8192, timed_iters=5, with_xla_flops=False,
        with_decode=False, model_overrides={"remat": "none"})
    lm_flash = _sub(run_lm_bench, use_flash=True)
    lm_jnp = _sub(run_lm_bench, use_flash=False, timed_iters=10,
                  with_xla_flops=False)
    extra["configs"]["transformer_lm"] = lm_flash
    # LM-small batch sweep (round-4 verdict item 6): the 0.36-MFU cell
    # had no sweep recording whether bigger batch was tried. Measured
    # round 5 (v5e): plain batch > 32 fails to compile (no remat, the
    # activation working set outgrows the compiler), but batch x
    # grad_accum (the scan splits the batch into microbatch-8 chunks)
    # climbs 0.28 -> 0.43 and plateaus at bs=512/A=64 — the committed
    # plateau, explained in EXPERIMENTS.md §8 (head_dim 64 halves the
    # MXU contraction fill on the ~40% of FLOPs in attention, and
    # d_model 512 carries 4x the elementwise-per-matmul overhead of
    # LM-large's 2048).
    if "error" not in lm_flash:
        lm_sweep = {}
        for bs, ga in ((16, 1), (32, 1), (32, 4), (64, 8), (128, 16),
                       (512, 64)):
            r = _sub(run_lm_bench, batch_size=bs, timed_iters=4,
                     with_xla_flops=False, with_decode=False,
                     trainer_overrides={"grad_accum": ga})
            lm_sweep[f"{bs}x{ga}"] = (
                {"batch": bs, "grad_accum": ga,
                 "tokens_per_sec": r["value"],
                 "mfu": r["extra"]["mfu"]}
                if "error" not in r else r)
        lm_flash["extra"]["batch_sweep"] = lm_sweep
    if "error" not in lm_flash and "error" not in lm_jnp:
        extra["flash_attention_delta"] = {
            "flash_tokens_per_sec": lm_flash["value"],
            "jnp_tokens_per_sec": lm_jnp["value"],
            "speedup": round(lm_flash["value"] / lm_jnp["value"], 3),
        }
    else:
        extra["flash_attention_delta"] = {
            "flash": lm_flash.get("error"), "jnp": lm_jnp.get("error")}
    extra["collectives"] = _sub(run_collectives_bench)
    # Tuned-vs-default per family (tpu_ddp/tune/): records whether the
    # autotuner finds anything the hand-tuned defaults miss, and proves
    # its never-ship-a-regression guard on the real chip.
    extra["autotune"] = _sub(run_autotune_probe)
    # Memory-policy probe (tpu_ddp/memory/): what remat buys (or costs)
    # on the big-activation ResNet-50 cell, measured on this chip with
    # the committed sweep's own protocol.
    extra["remat"] = _sub(run_remat_probe)
    # Bucketized-overlap probe (tpu_ddp/parallel/overlap.py): fused rung
    # vs 25 MB buckets + sharded update on the resnet50 cell — the
    # compiled-HLO overlap verdict plus, on TPU, the steps/sec delta.
    extra["overlap"] = _sub(run_overlap_probe)
    # Serving probe (tpu_ddp/serve/): continuous-vs-static goodput at
    # 1.5x saturation — the serve subsystem's headline ordering.
    extra["serve"] = _sub(run_serve_probe)
    # Long-context probe (serve/long_context.py): tiered-vs-resident
    # TTFT/token at 8x hot-tier oversubscription + bf16 cold-codec
    # bitwise parity; the enforced <=1.2x gate lives in
    # scripts/long_context_sweep.py.
    extra["long_context"] = _sub(run_long_context_probe)
    # Fleet probe (tpu_ddp/fleet/): disagg+prefix vs the single engine
    # at equal simulated hardware — the p99-TTFT ordering under
    # oversubscription.
    extra["fleet"] = _sub(run_fleet_probe)
    # Fleet-resilience probe (fleet/resilience.py): goodput with 1 of
    # 3 replicas chaos-crashed mid-load vs healthy — the >= 0.55 ratio
    # plus backoff re-admission are the recorded claims.
    extra["fleet_resilience"] = _sub(run_fleet_resilience_probe)
    # Autoscale probe (fleet/autoscale.py): scale-up reaction time,
    # boot-from-push vs checkpoint restart — push must be faster AND
    # join at the fleet's current published version.
    extra["fleet_autoscale"] = _sub(run_fleet_autoscale_probe)
    # Graph-audit probe (tpu_ddp/analysis/): donation/precision/
    # lockstep-determinism verdicts on this chip's own lowered step
    # programs (TPU schedules emit async collective pairs the CPU
    # tier never compiles).
    extra["graph_audit"] = _sub(run_graph_audit_probe)
    # MoE probe (parallel/moe.py): routing-health counters — dropped-
    # token fraction + per-expert load/imbalance per routed layer —
    # on the tiny MoE preset after a few train steps; the enforced
    # MoE-vs-dense gates live in scripts/moe_sweep.py.
    extra["moe"] = _sub(run_moe_probe)
    # Run-to-run variance control (round-3 verdict item 2): every
    # timed number is the MEDIAN of >= 3 consecutive chained windows,
    # with the raw per-window samples recorded next to it
    # (extra.samples / extra.sample_spread_pct), so a cross-round delta
    # is attributable — a wide spread marks a noise-dominated cell,
    # a tight spread makes the median trustworthy.
    extra["variance_note"] = (
        "each number is the median of >= 3 chained windows; "
        "extra.samples holds the per-window avg_iter_s and "
        "extra.sample_spread_pct the (max-min)/median spread")
    return result


def compact_headline(result: dict) -> dict:
    """The ONE stdout line the driver parses. Round 2's lesson: the full
    nested result outgrew the driver's bounded tail capture and the
    headline fields were truncated away (BENCH_r02.json ``parsed: null``).
    Full details now go to ``experiments/bench_full.json``; stdout gets
    only metric/value/unit/vs_baseline plus the per-family MFU summary."""
    extra = result.get("extra", {})
    configs = extra.get("configs", {})

    def _cfg_mfu(name):
        cfg = configs.get(name, {})
        best = cfg.get("extra", {}).get("mfu")
        # The sweep lives under extra on success, top-level when the
        # headline cell errored (e.g. OOM at the default batch) — the
        # surviving sweep cells must still feed the compact headline.
        sweep = {**cfg.get("batch_sweep", {}),
                 **cfg.get("extra", {}).get("batch_sweep", {})}
        for r in sweep.values():
            m = r.get("mfu") if isinstance(r, dict) else None
            if m is not None and (best is None or m > best):
                best = m
        return best

    mfus = {"vgg11": extra.get("mfu"),
            "resnet50": _cfg_mfu("resnet50_imagenet"),
            "transformer_lm": _cfg_mfu("transformer_lm"),
            "transformer_lm_long": _cfg_mfu("transformer_lm_long"),
            "transformer_lm_large": _cfg_mfu("transformer_lm_large")}
    sweep = extra.get("batch_sweep", {})
    for bs, r in sweep.items():
        m = r.get("mfu") if isinstance(r, dict) else None
        if m is not None and (mfus["vgg11"] is None or m > mfus["vgg11"]):
            mfus["vgg11"] = m
    mfus = {k: v for k, v in mfus.items() if v is not None}
    return {
        "metric": result["metric"],
        "value": result["value"],
        "unit": result["unit"],
        "vs_baseline": result["vs_baseline"],
        "mfu": extra.get("mfu"),
        "best_mfu": (max(mfus.values()) if mfus else None),
        "mfu_by_family": mfus,
        "details": "experiments/bench_full.json",
    }


def cli() -> int:
    """``python bench.py``: run everything, write the full record, print
    the one headline line — and exit non-zero, listing the cells on
    stderr, when any sub-benchmark failed."""
    from tpu_ddp.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = main()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "experiments")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "bench_full.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(compact_headline(result)))
    failed = failed_cells(result)
    if failed:
        print(f"bench: {len(failed)} cell(s) failed:", file=sys.stderr)
        for line in failed:
            print(f"  {line}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(cli())

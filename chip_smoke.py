"""chip_smoke.py — does the system still start on the chip?

One process, run as ``python chip_smoke.py`` from the checkout root on a
machine with a TPU. It drives what a user drives — ``run_part`` for the
sync ladder, ``LMTrainer``, ``ServeEngine`` — at the full width of the
models the repo benchmarks (VGG-11 at the reference's global batch 256;
TransformerLM-large, 12 layers, d_model 2048, ~740M parameters), with
random weights made from a seed, and checks the results by the repo's
own means: the step guard's finite-loss check, the retrace sentinel, the
``jax.numpy`` references of the seven Pallas kernels, ``generate()``.

    P0  device: platform pinned to tpu, device_kind in both peak tables,
        compile-cache directory, native libraries rebuilt from source
    P1  the ladder: run_part("part1") and run_part("part3") on VGG-11
    P2  the seven Pallas kernels, compiled, against their references
    P3  LMTrainer on TransformerLM-large with the flash kernel, 5 steps
    P4  ServeEngine on TransformerLM-large, bf16 then int8 decode, the
        paged decode kernel in the compiled step (and the gather body
        in a head_dim 64 model's)
    P5  four chips (skipped on fewer): the five rungs agree on one step
        at dp=4, part3 end to end, one LM-large step over dp=2 x tp=2

``python chip_smoke.py P2 P5`` runs a subset (P0 always runs); with no
argument every phase runs, which is what the driver does.

It fails rather than degrades: with no TPU it exits non-zero and prints
no result, and so it does in a directory that holds nothing else of the
repo; a failed phase is listed and the exit code is 1. Each phase prints
one JSON line (platform, device_kind, device count, compile seconds,
steady-state seconds), then comes the summary line (per-phase status and
times, ending ``"claim": null``), and the last line of stdout is the
result the driver reads, with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The full record goes to ``chiprun_out/chip_smoke.json``. Every time in
it is an observation of this smoke run at smoke sizes, not a benchmark
number: nothing here claims a speed.
"""

from __future__ import annotations

import contextlib
import gc
import io
import json
import math
import os
import re
import subprocess
import sys
import time
import traceback
import types
from pathlib import Path
from unittest import mock

HERE = Path(__file__).resolve().parent
OUT_DIR = HERE / "chiprun_out"
PHASES = ("P1", "P2", "P3", "P4", "P5")
SEED = 0
MOSAIC_CALL = "tpu_custom_call"

# Full-width sizes. The CPU debug harness (a builder's scratch script)
# shrinks these; the smoke itself never does.
PLATFORM = "tpu"
LADDER_ITERS = 40            # iterations 0..39: the 1..39 timing window
LADDER_BATCH = 256           # the reference's global batch
LM_PRESET = "TransformerLM-large"
LM_SEQ_LEN = 2048
LM_BATCH = 4                 # bench.py's LM-large microbatch
LM_STEPS = 5
SERVE_PROMPT_LENS = (64, 128, 200, 301, 400, 512)
SERVE_NEW_TOKENS = 32
SERVE_AGREE_MIN = 26        # of the 32, with generate(), first prompt (PR 21)
SERVE_GATHER_PRESET = "TransformerLM-small"     # head_dim 64
FLASH_SHAPES = ((1, 2048, 16, 128), (1, 1000, 16, 128))  # (B, L, H, D)
# LM-large's four decode matmuls (K -> N) at M = 8 live rows.
INT8_SHAPES = ((2048, 6144), (2048, 8192), (8192, 2048), (2048, 32000))
# Paged decode attention: (layers, blocks, block, KV heads, head_dim,
# slots, Q heads, blocks per slot) and the slots' lengths, one a full
# table, one a single token, the rest on and around page boundaries.
PAGED_SHAPE = (2, 8 * 64 + 1, 16, 2, 128, 8, 24, 64)
PAGED_LENGTHS = (1024, 1, 16, 17, 129, 511, 700, 33)
# state layers, slots, heads, head_dim, N, groups: two 2 MB tiles a slot
STATE_SHAPE = (2, 4, 128, 64, 128, 1)
# The expert layer's two grouped products at the hybrid cell's widths
# (rows, k, n), over four experts: one empty, one of more rows than a
# trip takes, one that starts inside a packed register, and 441 rows in
# no group.
GROUPED_SHAPES = ((640, 4096, 1536), (640, 768, 4096))
GROUPED_SIZES = (9, 0, 150, 40)
VGG_LEAF = (3, 3, 256, 512)          # a VGG-11 conv kernel
VGG_ACTIVATION = (256, 32, 32, 64)   # first conv output at batch 256

# Tolerances, fixed beforehand from the dtypes: largest absolute error
# over the largest absolute reference value. bf16 keeps 8 significant
# bits (unit roundoff 2^-8 = 3.9e-3); the flash kernel and its reference
# round the probabilities at different points, a few roundoffs forward
# and about twice that through the two extra roundings of the backward.
# f32 paths differ only in summation order.
TOL_BF16 = 2e-2
TOL_BF16_GRAD = 4e-2
TOL_F32_REDUCE = 1e-3       # sums over up to 2.6e5 rows (BatchNorm)
TOL_F32_DOT = 1e-4          # f32 accumulation over K <= 8192
TOL_F32_ELEMENTWISE = 1e-6  # the SGD chain
# One bf16 train step from one state: the rungs run different programs
# over the same batch, so updated parameters differ by gradient
# rounding times the learning rate (EXPERIMENTS.md §1 measured 2.3e-4
# at dp=1 on parameters of scale ~1).
TOL_LADDER_BF16 = 1e-3


def _die(msg: str) -> None:
    """No chip, no result: a message on stderr and a non-zero exit."""
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def result_line(ok: bool, device: dict) -> str:
    """The last line of stdout: the driver accepts exactly these keys,
    with the device as jax reports it (``p0_device``'s record)."""
    return json.dumps({"ok": ok,
                       "device": {"platform": device["platform"],
                                  "kind": device["device_kind"],
                                  "count": device["devices"]}})


def _max_err(got, want, relative: bool) -> float:
    """Largest |got - want| over a pytree, in float32; ``relative``
    divides each leaf's by its largest |want|."""
    import jax
    import numpy as np

    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                    strict=True):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        if g.shape != w.shape:
            raise AssertionError(f"shape {g.shape} != {w.shape}")
        if not np.all(np.isfinite(g)):
            raise AssertionError("non-finite output")
        err = float(np.max(np.abs(g - w)))
        if relative:
            err /= max(float(np.max(np.abs(w))), 1e-30)
        worst = max(worst, err)
    return worst


def _assert_kernels_compiled() -> None:
    from tpu_ddp.ops.pallas import interpret_mode

    if interpret_mode():
        raise AssertionError("interpret_mode() is True: the Pallas "
                             "kernels would run in the interpreter")


def _assert_mosaic(text: str, what: str) -> None:
    if MOSAIC_CALL not in text:
        raise AssertionError(
            f"{what}: no Mosaic custom call in the program — the Pallas "
            "kernel was swapped for another path")


# ---- P0 ------------------------------------------------------------------

def p0_device() -> dict:
    """Device, compile cache and the native build. Runs before any
    other phase; a missing or unknown chip ends the smoke here."""
    import jax

    from tpu_ddp.utils import flops
    from tpu_ddp.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != PLATFORM:
        _die(f"jax.devices()[0].platform is {dev.platform!r}, "
             f"not {PLATFORM!r}")
    for table in (flops.peak_tflops, flops.device_hbm_gbps):
        value, source = table(dev)
        if value is None or not source.startswith("device_kind"):
            _die(f"device_kind {dev.device_kind!r} is not in "
                 f"{table.__name__}'s table ({source})")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "devices": len(jax.devices()),
            "device_order": [
                {"id": d.id, "coords": list(getattr(d, "coords", ()))}
                for d in jax.devices()],
            "compile_cache_dir": enable_compile_cache()}


def p0_native() -> dict:
    """Rebuild native/*.so from the committed sources (the chip tool
    copies the working tree, stale binaries included) and load both."""
    from tpu_ddp.data import loader, native, text

    subprocess.run(["make", "-C", str(HERE / "native"), "clean", "all"],
                   check=True, capture_output=True, text=True,
                   timeout=300)
    if not native.available():
        raise AssertionError(f"image library: {native.build_error()}")
    if not text.native_available():
        raise AssertionError("text library did not load")
    # What the ladder's loaders will use (TPU_DDP_NATIVE_LOADER).
    picked = loader._pick_loader_cls(None).__name__
    return {"native_build": "rebuilt",
            "ladder_data_path": ("native" if picked == "NativeDataLoader"
                                 else "numpy")}


# ---- P1 ------------------------------------------------------------------

def _run_ladder_part(part: str, argv: list, step_name: str,
                     dp_slots: int) -> dict:
    """One ``run_part`` call at full width, checked from what it prints
    (a user sees nothing else) and from the retrace sentinel."""
    sys.path.insert(0, str(HERE / "parts"))
    from common import run_part

    from tpu_ddp.analysis.retrace import no_retrace

    captured = io.StringIO()
    t0 = time.perf_counter()
    try:
        with no_retrace(watch=(step_name,)) as compiles, \
                contextlib.redirect_stdout(captured):
            rc = run_part(part, argv)
    finally:
        out = captured.getvalue()
        sys.stdout.write(out)
    wall = time.perf_counter() - t0
    if rc != 0:
        raise AssertionError(f"{part}: run_part returned {rc}")
    head = re.search(rf"\[{part}\] strategy=(\S+) .*dp_slots=(\d+) "
                     r"per-node batch=(\d+) platform=(\S+)", out)
    if head is None:
        raise AssertionError(f"{part}: no configuration line")
    if head.group(4) != PLATFORM:
        raise AssertionError(f"{part}: printed platform={head.group(4)}")
    if int(head.group(2)) != dp_slots:
        raise AssertionError(f"{part}: dp_slots={head.group(2)}, "
                             f"expected {dp_slots}")
    if int(head.group(3)) != LADDER_BATCH:
        raise AssertionError(f"{part}: batch {head.group(3)} != "
                             f"{LADDER_BATCH}")
    # The step guard checks every step's loss and gradients in the
    # graph and reports any non-finite one here.
    if "[guard]" in out:
        raise AssertionError(f"{part}: the step guard skipped a step")
    losses = [float(m) for m in
              re.findall(r"\[epoch 0, iter \d+\] loss: (\S+)", out)]
    test = re.search(r"Test set: average loss (\S+),", out)
    if not losses or test is None:
        raise AssertionError(f"{part}: no loss / test lines")
    losses.append(float(test.group(1)))
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"{part}: non-finite loss in {losses}")
    stats = re.search(rf"\[{part}\] epoch 0: avg iter (\S+)s over (\d+) "
                      r"timed iters; (\d+) iters total", out)
    if stats is None or int(stats.group(2)) < 1:
        raise AssertionError(f"{part}: empty timing window")
    if int(stats.group(3)) != LADDER_ITERS:
        raise AssertionError(f"{part}: {stats.group(3)} iterations, "
                             f"expected {LADDER_ITERS}")
    if compiles.counts != {step_name: 1}:
        raise AssertionError(f"{part}: train step compiles "
                             f"{compiles.counts}, expected one")
    return {"strategy": head.group(1), "dp_slots": dp_slots,
            "window_losses": losses[:-1], "test_loss": losses[-1],
            "timed_iters": int(stats.group(2)),
            "compile_s": round(compiles.compile_seconds, 2),
            "steady_s_per_step": float(stats.group(1)),
            "wall_s": round(wall, 2)}


def _ladder_env():
    """The one knob the ladder runs under: an iteration cap that still
    covers the iteration-1..39 timing window."""
    return mock.patch.dict(os.environ,
                           {"TPU_DDP_MAX_ITERS": str(LADDER_ITERS)})


def p1_ladder() -> dict:
    import jax

    from tpu_ddp.utils.profiling import DDP_TRAIN_STEP

    with _ladder_env():
        part1 = _run_ladder_part("part1", [], DDP_TRAIN_STEP, 1)
        part3 = _run_ladder_part("part3", ["--num-nodes", "1"],
                                 DDP_TRAIN_STEP, len(jax.devices()))
    return {"part1": part1, "part3": part3,
            "compile_s": round(part1["compile_s"] + part3["compile_s"], 2),
            "steady_s_per_step": {"part1": part1["steady_s_per_step"],
                                  "part3": part3["steady_s_per_step"]}}


# ---- P2 ------------------------------------------------------------------

def p2_kernels() -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_ddp.analysis.retrace import count_compiles
    from tpu_ddp.models.vgg import batch_norm
    from tpu_ddp.ops.optim import SGD
    from tpu_ddp.models.decode import attend_cached
    from tpu_ddp.ops.pallas import (batch_norm_relu, flash_attention,
                                    int8_matmul)
    from tpu_ddp.models.hybrid import advance_state
    from tpu_ddp.ops.pallas.grouped_matmul import grouped_matmul
    from tpu_ddp.ops.pallas.paged_attention import paged_decode_attention
    from tpu_ddp.ops.pallas.ssm_state_step import ssm_state_step
    from tpu_ddp.ops.quant import quantize_weight
    from tpu_ddp.parallel.ring_attention import full_attention

    _assert_kernels_compiled()
    checks: dict = {}
    steady = 0.0

    def check(name, kernel_fn, ref_fn, args, tol):
        nonlocal steady
        compiled = jax.jit(kernel_fn).lower(*args).compile()
        _assert_mosaic(compiled.as_text(), name)
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
        t0 = time.perf_counter()
        got = jax.block_until_ready(compiled(*args))
        steady += time.perf_counter() - t0
        err = _max_err(got, want, relative=True)
        checks[name] = {"err": float(f"{err:.3g}"), "tol": tol}
        if not err <= tol:
            raise AssertionError(f"{name}: error {err:.3g} > {tol}")

    keys = iter(jax.random.split(jax.random.key(SEED), 64))
    with count_compiles() as compiles:
        for shape in FLASH_SHAPES:
            q, k, v = (jax.random.normal(next(keys), shape, jnp.bfloat16)
                       for _ in range(3))
            cot = jax.random.normal(next(keys), shape, jnp.float32)

            def grads(attn, q, k, v, cot):
                return jax.grad(
                    lambda q, k, v: jnp.sum(
                        attn(q, k, v, True).astype(jnp.float32) * cot),
                    argnums=(0, 1, 2))(q, k, v)

            tag = f"flash L={shape[1]}"
            check(f"{tag} fwd", lambda q, k, v: flash_attention(
                q, k, v, True), lambda q, k, v: full_attention(
                    q, k, v, True), (q, k, v), TOL_BF16)
            check(f"{tag} grad",
                  lambda *a: grads(flash_attention, *a),
                  lambda *a: grads(full_attention, *a),
                  (q, k, v, cot), TOL_BF16_GRAD)

        for kdim, ndim in INT8_SHAPES:
            qw = quantize_weight(
                jax.random.normal(next(keys), (kdim, ndim), jnp.float32))
            x = jax.random.normal(next(keys), (8, 1, kdim), jnp.bfloat16)
            check(f"int8_matmul {kdim}->{ndim}",
                  lambda x, q, s: int8_matmul(x, q, s),
                  lambda x, q, s: jnp.dot(
                      x, q.astype(x.dtype),
                      preferred_element_type=jnp.float32) * s,
                  (x, qw.q, qw.s), TOL_F32_DOT)

        layers, blocks, bs, kvh, hd, slots, heads, bps = PAGED_SHAPE
        pools = [jax.random.normal(next(keys),
                                   (layers, blocks, bs, kvh * hd),
                                   jnp.bfloat16) for _ in range(2)]
        tables = 1 + jax.random.permutation(
            next(keys), blocks - 1).reshape(slots, bps).astype(jnp.int32)
        lengths = jnp.asarray(PAGED_LENGTHS, jnp.int32)
        q = jax.random.normal(next(keys), (slots, heads, hd), jnp.bfloat16)

        def gathered(q, pk, pv, tables, lengths):
            view = (slots, bps * bs, kvh, hd)
            return attend_cached(
                types.SimpleNamespace(head_dim=hd), q[:, None],
                pk[1][tables].reshape(view), pv[1][tables].reshape(view),
                (lengths - 1)[:, None])[:, 0]

        check("paged_decode_attention",
              lambda q, pk, pv, t, n: paged_decode_attention(
                  q, pk, pv, t, n, layer=1, kv_heads=kvh),
              gathered, (q, *pools, tables, lengths), TOL_BF16)

        layers, slots, heads, hd, n, groups = STATE_SHAPE
        pool = jax.random.normal(next(keys), (layers, slots, heads, hd, n))
        decay = jax.random.uniform(next(keys), (slots, heads))
        dtx = jax.random.normal(next(keys), (slots, heads, hd))
        b, c = (jax.random.normal(next(keys), (slots, groups, n))
                for _ in range(2))
        active = jnp.arange(slots) != 2       # slot 2 rides along

        def plain(pool, *args):
            y, new = advance_state(pool[1], *args)
            return (jnp.where(active[:, None, None], y, 0.0),
                    pool.at[1].set(jnp.where(
                        active[:, None, None, None], new, pool[1])))

        def in_pool(pool, *args):
            return ssm_state_step(pool, *args, layer=1, active=active)

        # The state bit for bit (the lane broadcast through the MXU is
        # exact, ops/pallas/ssm_state_step.py); y by the order of a sum
        # over N.
        check("ssm_state_step state", lambda *a: in_pool(*a)[1],
              lambda *a: plain(*a)[1], (pool, decay, dtx, b, c), 0.0)
        check("ssm_state_step y", lambda *a: in_pool(*a)[0],
              lambda *a: plain(*a)[0], (pool, decay, dtx, b, c),
              TOL_F32_ELEMENTWISE)

        sizes = jnp.asarray(GROUPED_SIZES, jnp.int32)
        for m, kdim, ndim in GROUPED_SHAPES:
            rows = jax.random.normal(next(keys), (m, kdim), jnp.bfloat16)
            w = (jax.random.normal(next(keys), (len(GROUPED_SIZES), kdim,
                                                ndim), jnp.float32)
                 * kdim ** -0.5).astype(jnp.bfloat16)
            in_group = (jnp.arange(m) < sum(GROUPED_SIZES))[:, None]
            # lax.ragged_dot leaves the rows past the last group
            # undefined; the kernel writes zeros there
            check(f"grouped_matmul {kdim}->{ndim}", grouped_matmul,
                  lambda r, w, s: jnp.where(in_group, jax.lax.ragged_dot(
                      r, w, s, preferred_element_type=jnp.float32), 0.0),
                  (rows, w, sizes), TOL_F32_DOT)

        tree = {"w": jax.random.normal(next(keys), VGG_LEAF, jnp.float32),
                "b": jax.random.normal(next(keys), VGG_LEAF[-1:],
                                       jnp.float32)}
        grad_tree = jax.tree.map(lambda p: 0.1 * p + 0.01, tree)

        def sgd_steps(opt, params, grads_):
            state = opt.init(params)
            for _ in range(2):      # second step exercises momentum
                params, state = opt.apply(params, grads_, state)
            return params, state["momentum"]

        check("fused_sgd_step",
              lambda p, g: sgd_steps(SGD(use_pallas=True), p, g),
              lambda p, g: sgd_steps(SGD(use_pallas=False), p, g),
              (tree, grad_tree), TOL_F32_ELEMENTWISE)

        chan = VGG_ACTIVATION[-1]
        x = (jax.random.normal(next(keys), VGG_ACTIVATION, jnp.float32)
             * 2 + 0.5).astype(jnp.bfloat16)
        scale = jax.random.uniform(next(keys), (chan,), minval=0.5,
                                   maxval=1.5)
        bias = 0.1 * jax.random.normal(next(keys), (chan,))
        cot = jax.random.normal(next(keys), VGG_ACTIVATION, jnp.float32)

        def bn_ref(x, scale, bias):
            return jnp.maximum(batch_norm(x, scale, bias), 0)

        def bn_all(fn, x, scale, bias, cot):
            y, vjp = jax.vjp(fn, x, scale, bias)
            return y, vjp(cot.astype(y.dtype))

        # y and dx come back in bf16, dscale/dbias in f32.
        check("batch_norm_relu fwd+grad",
              lambda *a: bn_all(batch_norm_relu, *a),
              lambda *a: bn_all(bn_ref, *a),
              (x, scale, bias, cot), TOL_BF16)
        check("batch_norm_relu f32",
              lambda x, s, b: batch_norm_relu(x.astype(jnp.float32), s, b),
              lambda x, s, b: bn_ref(x.astype(jnp.float32), s, b),
              (x, scale, bias), TOL_F32_REDUCE)
    return {"checks": checks,
            "compile_s": round(compiles.compile_seconds, 2),
            "steady_s": round(steady, 4)}


# ---- P3 ------------------------------------------------------------------

def _lm_steps(trainer, batch: int, steps: int) -> dict:
    """``steps`` train steps on one fixed seeded batch: finite losses,
    the flash kernel in the program, one compile."""
    import jax
    import numpy as np

    from tpu_ddp.analysis.retrace import no_retrace
    from tpu_ddp.train.lm import make_lm_batch
    from tpu_ddp.utils.profiling import LM_TRAIN_STEP

    model = trainer.model
    state = trainer.init_state(seed=SEED)
    tokens = np.random.default_rng(SEED).integers(
        0, model.vocab_size, size=(batch, model.max_seq_len + 1))
    x, y = trainer.put_batch(*make_lm_batch(tokens))
    _assert_mosaic(trainer.lower_train_step(state, x, y).as_text(),
                   "LM train step")
    losses, times = [], []
    with no_retrace(watch=(LM_TRAIN_STEP,)) as compiles:
        for _ in range(steps):
            t0 = time.perf_counter()
            state, loss = trainer.train_step(state, x, y)
            losses.append(float(np.mean(np.asarray(
                jax.block_until_ready(loss)))))
            times.append(time.perf_counter() - t0)
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite LM loss in {losses}")
    if compiles.counts != {LM_TRAIN_STEP: 1}:
        raise AssertionError(f"LM train step compiles {compiles.counts}, "
                             "expected one")
    params = sum(int(p.size) for p in jax.tree.leaves(state.params))
    return {"params": params, "losses": [round(v, 4) for v in losses],
            "compile_s": round(compiles.compile_seconds, 2),
            "first_step_s": round(times[0], 2),
            "steady_s_per_step": (round(float(np.mean(times[1:])), 4)
                                  if steps > 1 else None)}


def p3_lm_trainer() -> dict:
    import jax

    from tpu_ddp.models import make_transformer
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer

    _assert_kernels_compiled()
    model = make_transformer(LM_PRESET, max_seq_len=LM_SEQ_LEN,
                             use_flash=True, remat="none")
    trainer = LMTrainer(model, make_mesh(jax.devices()[:1]))
    out = _lm_steps(trainer, LM_BATCH, LM_STEPS)
    if not out["losses"][-1] < out["losses"][0]:
        raise AssertionError(f"LM loss did not fall: {out['losses']}")
    return {"model": model.name, "layers": model.num_layers,
            "d_model": model.d_model, "batch": LM_BATCH,
            "seq_len": LM_SEQ_LEN, **out}


# ---- P4 ------------------------------------------------------------------

def _decode_attention(engine) -> str:
    """How the engine's COMPILED decode step attends: through the paged
    kernel, in place, or over the gathered ``max_seq_len`` view."""
    text = engine.lower_decode_step().compile().as_text()
    return "paged_decode_attn" if "paged_decode_attn" in text else "gather"


def p4_serve() -> dict:
    import jax
    import numpy as np

    from tpu_ddp.analysis.retrace import no_retrace
    from tpu_ddp.models import generate, make_transformer
    from tpu_ddp.ops.pallas import paged_attention
    from tpu_ddp.serve import ServeEngine
    from tpu_ddp.utils.profiling import SERVE_DECODE, SERVE_PREFILL

    _assert_kernels_compiled()
    model = make_transformer(LM_PRESET, max_seq_len=LM_SEQ_LEN)
    params = model.init(jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, model.vocab_size, size=n)
               for n in SERVE_PROMPT_LENS]
    out: dict = {"model": model.name, "layers": model.num_layers,
                 "compute_dtype": np.dtype(model.compute_dtype).name}
    compile_s = 0.0
    for quant in ("none", "int8"):
        engine = ServeEngine(model, params, decode_quant=quant)
        geometry = (engine.num_slots, engine.block_size,
                    engine.prefill_chunk)
        if geometry != (8, 16, 32):
            raise AssertionError(f"serve geometry {geometry} is not the "
                                 "default (8, 16, 32)")
        if quant == "int8":
            # qdot took the kernel branch (ops/quant.py), not the XLA
            # reference below it.
            _assert_mosaic(engine.lower_decode_step().as_text(),
                           "int8 decode step")
            _assert_mosaic(engine.lower_prefill_step().as_text(),
                           "int8 prefill step")
        # Warm both programs (two prefill chunks, one decode step) so
        # compilation stays out of the timed window.
        with no_retrace(watch=(SERVE_DECODE, SERVE_PREFILL)) as warm:
            engine.submit(prompts[0][:engine.prefill_chunk + 8], 2)
            engine.run()
        if warm.counts != {SERVE_DECODE: 1, SERVE_PREFILL: 1}:
            raise AssertionError(f"{quant}: decode + prefill compiles "
                                 f"{warm.counts}, expected two")
        # head_dim 128, block 16, bf16 pool: inside the paged kernel's
        # predicate, so the compiled step must hold the kernel.
        attends = _decode_attention(engine)
        if attends != "paged_decode_attn":
            raise AssertionError(f"{quant}: the compiled decode step of "
                                 f"{model.name} attends by {attends}")
        with no_retrace(watch=(SERVE_DECODE, SERVE_PREFILL),
                        max_compiles=0):
            t0 = time.perf_counter()
            reqs = [engine.submit(p, SERVE_NEW_TOKENS, seed=i)
                    for i, p in enumerate(prompts)]
            steps = engine.run()
            wall = time.perf_counter() - t0
        for r in reqs:
            if not r.done or r.cancelled or r.shed or r.quarantined:
                raise AssertionError(f"{quant}: request {r.rid} did not "
                                     "finish cleanly")
            if len(r.tokens) != SERVE_NEW_TOKENS:
                raise AssertionError(f"{quant}: request {r.rid} has "
                                     f"{len(r.tokens)} tokens")
            if not all(math.isfinite(lp) for lp in r.logprobs):
                raise AssertionError(f"{quant}: request {r.rid} has a "
                                     "non-finite logprob")
        generated = len(reqs) * SERVE_NEW_TOKENS
        cell = {"decode_attention": attends,
                "requests": len(reqs), "engine_steps": steps,
                "generated_tokens": generated,
                "compile_s": round(warm.compile_seconds, 2),
                "steady_s": round(wall, 3),
                "steady_s_per_token": round(wall / generated, 5)}
        compile_s += warm.compile_seconds
        if quant == "none":
            ref = np.asarray(generate(model, params, prompts[0][None],
                                      max_new_tokens=SERVE_NEW_TOKENS))[0]
            agree = int(np.sum(ref == np.asarray(reqs[0].tokens)))
            cell["tokens_agreeing_with_generate"] = agree
            # bf16 greedy streams part ways at the first near-tie; the
            # gather body agreed on 26 of 32 (PR 21).
            if agree < SERVE_AGREE_MIN:
                raise AssertionError(
                    f"only {agree} of {SERVE_NEW_TOKENS} greedy tokens "
                    f"agree with generate() (at least {SERVE_AGREE_MIN} "
                    "expected)")
        out["bf16" if quant == "none" else "int8"] = cell
        del engine, reqs
        gc.collect()
    # A model outside the predicate (head_dim 64) keeps the gather body:
    # reported, not hidden.
    small = make_transformer(SERVE_GATHER_PRESET, max_seq_len=LM_SEQ_LEN)
    engine = ServeEngine(small, small.init(jax.random.key(SEED)))
    inside = paged_attention.supports(small.head_dim, engine.block_size,
                                      engine.pool.k.dtype,
                                      small.compute_dtype)
    attends = _decode_attention(engine)
    if inside or attends != "gather":
        raise AssertionError(f"{small.name} (head_dim {small.head_dim}): "
                             f"predicate {inside}, attends by {attends}")
    out["outside_predicate"] = {"model": small.name,
                                "head_dim": small.head_dim,
                                "decode_attention": attends}
    out["compile_s"] = round(compile_s, 2)
    out["steady_s_per_token"] = {k: out[k]["steady_s_per_token"]
                                 for k in ("bf16", "int8")}
    return out


# ---- P5 ------------------------------------------------------------------

def _distinct_shard_devices(tree, want: int, what: str) -> None:
    """Every leaf bigger than one shard is split over ``want`` devices
    (not a replica on each)."""
    import jax

    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        on = len({s.device for s in shards})
        if on != want:
            raise AssertionError(f"{what}: a leaf sits on {on} devices, "
                                 f"expected {want}")
        if leaf.size >= want and shards[0].data.size >= leaf.size:
            raise AssertionError(f"{what}: leaf {leaf.shape} is "
                                 "replicated, not sharded")


def p5_four_chips() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_ddp.analysis.retrace import count_compiles
    from tpu_ddp.models import get_model, make_transformer
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.engine import Trainer
    from tpu_ddp.train.lm import LMTrainer
    from tpu_ddp.utils.config import TrainConfig
    from tpu_ddp.utils.profiling import DDP_TRAIN_STEP

    n = len(jax.devices())
    if n < 4:
        return {"skipped": f"{n} device(s) attached; P5 needs 4"}
    devices = jax.devices()[:4]
    mesh = make_mesh(devices)
    cfg = TrainConfig.preset("vgg11_cifar10")
    model = get_model(cfg.model, num_classes=cfg.num_classes,
                      compute_dtype=jnp.dtype(cfg.compute_dtype))
    rng = np.random.default_rng(SEED)
    images = rng.integers(0, 256, size=(LADDER_BATCH, 32, 32, 3)
                          ).astype(np.uint8)
    labels = rng.integers(0, 10, size=LADDER_BATCH).astype(np.int32)
    rungs = ("gather_scatter", "all_reduce", "fused", "zero", "fsdp")
    updated, losses, compile_s = {}, {}, 0.0
    with count_compiles() as compiles:
        for rung in rungs:
            trainer = Trainer(model, cfg, strategy=rung, mesh=mesh)
            state = trainer.init_state()
            xb, yb, wb = trainer.put_batch(images, labels)
            _distinct_shard_devices((xb, yb), 4, f"{rung} batch")
            if rung == "zero":
                _distinct_shard_devices(state.opt_state, 4,
                                        "zero optimizer state")
            if rung == "fsdp":
                _distinct_shard_devices(state.params, 4,
                                        "fsdp parameters")
            if rung == "fused":
                text = trainer.lower_train_step(
                    state, xb, yb, wb).compile().as_text()
                if "all-reduce" not in text:
                    raise AssertionError(
                        "compiled fused step has no all-reduce")
            state, loss = trainer.train_step(state, xb, yb, wb)
            losses[rung] = float(np.mean(np.asarray(loss)))
            updated[rung] = trainer.params_to_host(state)
            del trainer, state
    compile_s += compiles.compile_seconds
    if not all(math.isfinite(v) for v in losses.values()):
        raise AssertionError(f"non-finite rung loss: {losses}")
    # The ladder's invariant: from one state and one batch, every rung
    # applies the same update.
    spread = {r: _max_err(updated[r], updated["fused"], relative=False)
              for r in rungs}
    if max(spread.values()) > TOL_LADDER_BF16:
        raise AssertionError(f"rungs disagree after one step: {spread} "
                             f"(bound {TOL_LADDER_BF16})")
    del updated
    gc.collect()

    with _ladder_env():
        part3 = _run_ladder_part("part3", ["--num-nodes", "1"],
                                 DDP_TRAIN_STEP, n)
    compile_s += part3["compile_s"]

    lm = make_transformer(LM_PRESET, max_seq_len=LM_SEQ_LEN,
                          use_flash=True, remat="none")
    lm_trainer = LMTrainer(lm, make_mesh(devices, dp=2, mp=2))
    lm_step = _lm_steps(lm_trainer, LM_BATCH, 1)
    compile_s += lm_step["compile_s"]
    return {"rung_losses": losses,
            "max_abs_param_diff_vs_fused": spread,
            "ladder_bound_bf16": TOL_LADDER_BF16,
            "part3_dp4": part3, "lm_dp2_tp2": lm_step,
            "compile_s": round(compile_s, 2),
            "steady_s_per_step": {"part3": part3["steady_s_per_step"]}}


# ---- driver --------------------------------------------------------------

def main(argv: list) -> int:
    wanted = [a.upper() for a in argv] or list(PHASES)
    unknown = [p for p in wanted if p not in PHASES]
    if unknown:
        _die(f"unknown phase(s) {unknown}; choose from {PHASES}")
    # P0 starts before jax is imported: the platform is pinned to the
    # chip alone (a TPU machine may come with "tpu,cpu"), so a machine
    # without one fails instead of running on the CPU.
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and PLATFORM not in platforms.split(","):
        _die(f"JAX_PLATFORMS={platforms!r} does not name {PLATFORM!r}; "
             "this smoke runs on the TPU only")
    if not (HERE / "tpu_ddp").is_dir():
        _die(f"{HERE} holds no tpu_ddp package: there is no program "
             "here to drive")
    os.environ["JAX_PLATFORMS"] = PLATFORM
    sys.path.insert(0, str(HERE))
    import jax

    device = p0_device()
    fixed = {k: device[k] for k in ("platform", "device_kind", "devices")}
    record: dict = {}
    failed: list = []

    def peak_hbm():
        stats = jax.devices()[0].memory_stats() or {}
        return stats.get("peak_bytes_in_use")

    def run(name, fn):
        t0 = time.perf_counter()
        try:
            info = fn()
            ok = True
        except Exception as e:  # noqa: BLE001 — boundary: listed below
            traceback.print_exc()
            info = {"error": f"{type(e).__name__}: {e}"}
            ok = False
            failed.append(name)
        line = {"phase": name, "ok": ok, **fixed, **info,
                "phase_wall_s": round(time.perf_counter() - t0, 2),
                "peak_hbm_bytes_so_far": peak_hbm()}
        print(json.dumps(line), flush=True)
        record[name] = line
        gc.collect()

    run("P0", lambda: {**device, **p0_native()})
    phase_fns = {"P1": p1_ladder, "P2": p2_kernels, "P3": p3_lm_trainer,
                 "P4": p4_serve, "P5": p5_four_chips}
    for name in PHASES:
        if name in wanted:
            run(name, phase_fns[name])

    OUT_DIR.mkdir(exist_ok=True)
    (OUT_DIR / "chip_smoke.json").write_text(json.dumps(record, indent=1))
    summary = {
        "failed": failed,
        "phases": {
            name: {"ok": rec["ok"],
                   "skipped": rec.get("skipped"),
                   "compile_s": rec.get("compile_s"),
                   "steady_s": (rec.get("steady_s_per_step")
                                or rec.get("steady_s_per_token")
                                or rec.get("steady_s")),
                   "wall_s": rec["phase_wall_s"]}
            for name, rec in record.items()},
        "times_are": "smoke observations, not benchmark numbers",
        "peak_hbm_bytes": peak_hbm(),
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    print(result_line(not failed, device), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

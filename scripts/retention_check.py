"""The power retention of a ``brumby`` configuration against its plain
float32 reference (benchmark/reference/brumby.py, the quadratic form), on
one chip at the configuration's widths and seeded random weights.

The benchmark's two check requests (made as the benchmark's runner makes
them for that seed) are served alone, through the runner's own check
(``benchmark/runners/serve_model.py`` ``_logprob_check``) against the
reference's full forward pass, by a ``ServeEngine`` at the cell's
geometry, four times:

- ``as_is``: the engine as it is;
- ``zero_carry``: every prefill chunk started from a zero state in place
  of the one the slot carries (the first request is 10 chunks of 512, so
  9 carries are lost);
- ``bf16_state``: ``S`` and ``z`` kept in bfloat16 (a model whose
  ``state_shapes`` say so), every product as before;
- ``reference_fp8``: the engine as it is against the reference with
  every weight rounded to float8 e4m3 as it is used (the nearest floating
  precision below the configuration's bf16, a scale a column:
  ``reference.rounded``): the control of the limit from below.

The first has to pass the traffic's ``logprob_tol``, each of the other
three to fail it.

    python scripts/retention_check.py --seed 2147483701 [--out DIR]

Prints one JSON record a seed, and each check's reading to stderr as it
comes; ``--out DIR`` also writes each record to
``DIR/retention_check_<seed>.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.lib.families import brumby as family  # noqa: E402
from benchmark.reference import brumby as reference  # noqa: E402
from benchmark.runners import serve_model  # noqa: E402
from tpu_ddp.models.retention import RetentionLM  # noqa: E402
from tpu_ddp.serve import ServeEngine  # noqa: E402
from tpu_ddp.serve import engine as engine_mod  # noqa: E402

CONFIG = ROOT / "benchmark/configs/brumby-14b-base-l8.json"
TRAFFIC = ROOT / "benchmark/traffic/serve-doc16.json"


def _zero_carry(state_chunk):
    """``engine.retention_state_chunk`` with every chunk's ``fresh``
    true."""
    def chunk(model, blk, x, state, si, slot, fresh, n_valid, pos):
        return state_chunk(model, blk, x, state, si, slot, True, n_valid,
                           pos)
    return chunk


@dataclasses.dataclass(frozen=True)
class Bf16State(RetentionLM):
    """The model with ``S`` and ``z`` kept in bfloat16: the planted
    fault (the engine reads the state up to float32 and writes it back
    down, so every product is as before)."""

    def state_shapes(self, num_slots: int) -> dict:
        return {k: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                for k, s in super().state_shapes(num_slots).items()}


def _check(model, params, config, traffic, seed, fmt=None):
    """The runner's check of ``seed`` on an engine of ``model``, against
    the reference with its weights rounded to ``fmt`` if given: (largest
    difference, seconds)."""
    geo = config["serve"]
    engine = ServeEngine(model, params, num_slots=geo["num_slots"],
                         block_size=geo["block_size"],
                         prefill_chunk=geo["prefill_chunk"])
    def log_probs(p, tokens):
        # The engine has served the check: its state pool (4.36 GB at the
        # cell's geometry) makes room for the reference, which with
        # ``fmt`` also holds a rounded copy of the head and of a layer.
        for a in engine.state.arrays.values():
            if not a.is_deleted():
                a.delete()
        return reference.log_probs(p, tokens, config, fmt=fmt)

    t = time.perf_counter()
    got = serve_model._logprob_check(
        engine, params, traffic["check"], model.vocab_size,
        np.random.default_rng(seed), log_probs)
    seconds = time.perf_counter() - t
    print(f"{model.name} {fmt or 'float32'}: {got['max_abs_diff']:.5f} "
          f"in {seconds:.0f} s", file=sys.stderr, flush=True)
    del engine
    gc.collect()        # its state pool, before the next engine makes one
    return got["max_abs_diff"], seconds


def run(seed: int, config: dict, traffic: dict) -> dict:
    geo = config["serve"]
    seed = seed % (2 ** 31 - 1)          # as the runner folds it
    model = family.build(config, max_seq_len=geo["max_seq_len"],
                         param_dtype=jnp.dtype(geo["param_dtype"]))
    params = jax.jit(model.init)(jax.random.key(seed))
    out = {"seed": seed, "logprob_tol": traffic["check"]["logprob_tol"],
           "new_tokens": traffic["check"]["new_tokens"], "seconds": {}}
    out["as_is"], out["seconds"]["as_is"] = _check(
        model, params, config, traffic, seed)
    real = engine_mod.retention_state_chunk
    engine_mod.retention_state_chunk = _zero_carry(real)
    try:
        out["zero_carry"], out["seconds"]["zero_carry"] = _check(
            dataclasses.replace(model, name=model.name + "-zero-carry"),
            params, config, traffic, seed)
    finally:
        engine_mod.retention_state_chunk = real
    fields = {f.name: getattr(model, f.name)
              for f in dataclasses.fields(model)}
    out["bf16_state"], out["seconds"]["bf16_state"] = _check(
        Bf16State(**dict(fields, name=model.name + "-bf16-state")),
        params, config, traffic, seed)
    out["reference_fp8"], out["seconds"]["reference_fp8"] = _check(
        model, params, config, traffic, seed, fmt="fp8")
    tol = out["logprob_tol"]
    out["verdict"] = {"as_is_within": out["as_is"] <= tol,
                      **{f"{k}_fails": out[k] > tol for k in
                         ("zero_carry", "bf16_state", "reference_fp8")}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--config", default=str(CONFIG))
    ap.add_argument("--traffic", default=str(TRAFFIC))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from tpu_ddp.utils.compile_cache import enable_compile_cache
    enable_compile_cache()      # the cell's programs, for a run after this
    config = json.loads(Path(args.config).read_text())
    traffic = json.loads(Path(args.traffic).read_text())
    for seed in args.seed:
        rec = run(seed, config, traffic)
        print(json.dumps(rec), flush=True)
        if args.out:
            out = Path(args.out)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"retention_check_{seed}.json").write_text(
                json.dumps(rec, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The learned sparse attention of a ``keye_vl2`` configuration against
its plain float32 reference (benchmark/reference/keye_vl2.py), on one
chip at the configuration's widths and seeded random weights:

1. *Selection.* The check prompt (``--tokens`` long, made as the
   benchmark's runner makes its first check prompt) through the
   program's own layer functions in the serving precision, the whole
   sequence at once: per layer the indexer's scores
   (``sparse_moe.index_scores``), the selection as a mask
   (``sparse_moe.selection_mask``), attention through it
   (``decode.attend_masked``), the block's MLP half; and through the
   reference. Per layer and query: the share of the reference's selected
   positions that the program also selects, and for every position the
   two disagree on, how far the reference's score of it lies from the
   reference's ``topk``-th score, over the spread of that row's scores;
   the same for each layer given the reference's own input (rounded to
   the serving precision), which leaves out the drift of the layers
   before it.
2. *Log-probabilities.* The benchmark's two check requests served alone,
   through the benchmark runner's own check
   (``benchmark/runners/serve_model.py`` ``_logprob_check``, the same
   prompts as the cell's check of that seed) against the reference's full
   forward pass and the traffic's ``logprob_tol``, by a ``ServeEngine`` of
   two slots at the configuration's ``max_seq_len``: as it is; with every
   position selected (``index_topk`` = ``max_seq_len``: dense attention
   through the same programs); and with the engine's weights rounded to
   float8 (e4m3, the nearest floating precision below bf16, a scale a
   column; the reference keeping them). Both faults have to fail the
   limit. Then the reference itself with its weights in float8 and in
   int8 against the reference, at the greedy tokens.

    python scripts/sparse_attention_check.py --seed 2147483701 3141592653

Prints a record a seed; ``--out DIR`` also writes each to
``DIR/sparse_check_<seed>.json``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark.lib.families import keye_vl2 as family  # noqa: E402
from benchmark.reference import keye_vl2 as reference  # noqa: E402
from benchmark.runners import serve_model  # noqa: E402
from tpu_ddp.models import decode, sparse_moe  # noqa: E402
from tpu_ddp.serve import ServeEngine  # noqa: E402

ROWS = 256      # queries of the program's pass taken together


@functools.partial(jax.jit, static_argnums=0)
def _program_layer(model, blk, x):
    """One layer of the program's math on the whole sequence ``x`` (1, L,
    dm): (x after the layer, the selection (L, L))."""
    L = x.shape[1]
    pos = jnp.arange(L)
    y = model.norm(x, blk["ln1"])
    q, k, v = model.qkv_proj(blk, y, pos)
    qi, ki, w = model.index_proj(blk, y, pos)
    rows = jnp.minimum(jnp.arange(-(-L // ROWS) * ROWS), L - 1).reshape(
        -1, ROWS)

    def part(pb):
        keep = sparse_moe.selection_mask(
            model, sparse_moe.index_scores(model, qi[:, pb], w[:, pb], ki),
            pb[None])
        return decode.attend_masked(model, q[:, pb], k, v, keep)[0], keep[0]

    o, keep = jax.lax.map(part, rows)
    o = o.reshape((-1,) + o.shape[2:])[:L][None]
    return (decode.block_finish(model, blk, x, o),
            keep.reshape(-1, L)[:L])


@jax.jit
def _compare(prog, ref_sel, scores):
    """Per query: the share of the reference's positions the program
    keeps, and the largest distance of a disputed position's reference
    score from the reference's cutoff, over the row's score spread."""
    L = prog.shape[0]
    ref = jnp.zeros_like(prog).at[
        jnp.arange(L)[:, None], jnp.where(ref_sel >= 0, ref_sel, L)].set(
            True, mode="drop")
    n_ref = ref.sum(-1)
    overlap = (prog & ref).sum(-1) / n_ref
    finite = jnp.isfinite(scores)
    cut = jnp.min(jnp.where(ref, scores, jnp.inf), -1)
    mean = jnp.sum(jnp.where(finite, scores, 0), -1) / finite.sum(-1)
    spread = jnp.sqrt(jnp.sum(jnp.where(finite, (scores - mean[:, None]) ** 2,
                                        0), -1) / finite.sum(-1))
    gap = jnp.where(prog != ref, jnp.abs(scores - cut[:, None]), 0.0)
    return overlap, jnp.max(gap, -1) / jnp.maximum(spread, 1e-30), \
        (prog != ref).sum(-1)


def selection(model, params, tokens, config) -> list:
    inputs = []
    _, ref_sel = reference.forward(params, jnp.asarray(tokens), config,
                                   inputs)
    x = model.embed(params, jnp.asarray(tokens)[None])
    out = []
    for li, blk in enumerate(params["blocks"]):
        x, keep = _program_layer(model, blk, x)
        scores = reference.layer_index_scores(blk, inputs[li], config)
        # the same layer given the reference's input: the indexer's own
        # rounding, without what the layers before it drifted
        _, alone = _program_layer(
            model, blk, inputs[li].astype(model.compute_dtype)[None])
        row = {"layer": li}
        for tag, mask in (("", keep), ("same_input_", alone)):
            overlap, gap, n_diff = (np.asarray(a) for a in
                                    _compare(mask, ref_sel[li], scores))
            row.update({f"{tag}min_overlap": float(overlap.min()),
                        f"{tag}mean_overlap": float(overlap.mean()),
                        f"{tag}rows_under_99": int((overlap < 0.99).sum()),
                        f"{tag}disputed_positions": int(n_diff.sum()),
                        f"{tag}max_gap_over_spread": float(gap.max())})
        out.append(row)
        del scores, alone
    return out


def _at(lp, full, n_prompt):
    at = np.arange(n_prompt - 1, len(full) - 1)
    return lp[at, full[at + 1]]


def log_probs(model, params, config, check, seed: int) -> dict:
    """The runner's check with the engine as it is, with dense attention,
    and with float8 weights in the engine; the reference in float8 and
    in int8 against itself."""
    geo = config["serve"]
    cache = {}

    def ref_lp(p, tokens, tag=""):
        key = (tag, np.asarray(tokens).tobytes())
        if key not in cache:
            cache[key] = reference.log_probs(p, tokens, config)
        return cache[key]

    def check_with(engine_model, engine_params):
        engine = ServeEngine(engine_model, engine_params, num_slots=2,
                             block_size=geo["block_size"],
                             prefill_chunk=geo["prefill_chunk"])
        handles, engine_submit = [], engine.submit

        def submit(*args, **kw):          # the check's requests, kept
            handles.append(engine_submit(*args, **kw))
            return handles[-1]

        engine.submit = submit
        got = serve_model._logprob_check(
            engine, params, check, model.vocab_size,
            np.random.default_rng(seed), ref_lp)
        fulls = [np.concatenate([h.prompt, h.tokens]).astype(np.int32)
                 for h in handles]
        del engine
        return got["max_abs_diff"], fulls

    out = {}
    out["sparse_vs_reference"], fulls = check_with(model, params)
    dense = dataclasses.replace(model, index_topk=geo["max_seq_len"])
    out["dense_vs_reference"], _ = check_with(dense, params)
    low = reference.rounded(params, "fp8")
    out["fp8_engine_vs_reference"], _ = check_with(model, low)
    del low
    for fmt in ("fp8", "int8"):
        low = reference.rounded(params, fmt)
        out[f"{fmt}_reference_vs_reference"] = max(
            float(np.max(np.abs(
                _at(ref_lp(low, jnp.asarray(full[:-1]), fmt), full, n)
                - _at(ref_lp(params, jnp.asarray(full[:-1])), full, n))))
            for n, full in zip(check["prompt_lens"], fulls))
        del low
    tol = check["logprob_tol"]
    out["passes"] = {k: v <= tol for k, v in out.items()}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", default=str(
        ROOT / "benchmark/configs/keye-vl-2.0-30b-a3b-l8e16.json"))
    parser.add_argument("--traffic", default=str(
        ROOT / "benchmark/traffic/serve-long16.json"))
    parser.add_argument("--seed", type=int, nargs="+", required=True)
    parser.add_argument("--out", help="a directory for the records")
    parser.add_argument("--selection", type=int, choices=(0, 1), default=1,
                        help="0: the log-probabilities only")
    args = parser.parse_args(argv)
    config = json.loads(Path(args.config).read_text())
    check = json.loads(Path(args.traffic).read_text())["check"]
    geo = config["serve"]
    model = family.build(config, max_seq_len=geo["max_seq_len"],
                         param_dtype=jnp.dtype(geo["param_dtype"]))
    for given in args.seed:
        seed = given % (2 ** 31 - 1)            # as the runner takes it
        params = jax.jit(model.init)(jax.random.key(seed))
        result = {"seed": given, "device": jax.devices()[0].device_kind,
                  "logprob_tol": check["logprob_tol"]}
        if args.selection:
            prompt = np.random.default_rng(seed).integers(
                0, model.vocab_size, size=check["prompt_lens"][0])
            result["selection"] = selection(model, params, prompt, config)
        result.update(log_probs(model, params, config, check, seed))
        del params
        if args.out:
            out = Path(args.out)
            out.mkdir(parents=True, exist_ok=True)
            (out / f"sparse_check_{given}.json").write_text(
                json.dumps(result, indent=1))
        print(json.dumps(result), flush=True)
    return 0

if __name__ == "__main__":
    sys.exit(main())

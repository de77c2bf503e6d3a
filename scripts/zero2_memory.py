"""Record ZeRO-2's accumulation-memory claim from the compiled programs.

Produces experiments/zero2_memory.json with, per (dp, grad_accum):
``temp_bytes`` of the compiled train step under opt_sharding zero1
(full-leaf f32 accumulation buffer, replicated per device) vs zero2
(dp-scattered f32 slices) — the buffer is the dominant temp at high A,
so the zero2/zero1 ratio should approach 1/dp plus the shared
activation floor. Platform-independent claim about the compiled
program (the pipeline_schedules.json methodology, EXPERIMENTS.md §4);
run on the virtual CPU mesh.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python scripts/zero2_memory.py
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

# Force the virtual 8-device CPU platform: this script counts compiled
# bytes, it never needs the chip.
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def measure(dp: int, grad_accum: int, sharding: str,
            d_model: int = 128, vocab: int = 1024) -> dict:
    import numpy as np

    from tpu_ddp.models.transformer import make_transformer
    from tpu_ddp.ops.optim import SGD
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer, make_lm_batch

    model = make_transformer("TransformerLM-tiny", max_seq_len=128,
                             num_layers=4, d_model=d_model,
                             d_ff=4 * d_model, vocab_size=vocab)
    mesh = make_mesh(jax.devices()[:dp], dp=dp)
    tr = LMTrainer(model, mesh, grad_accum=grad_accum,
                   opt_sharding=sharding,
                   optimizer=SGD(learning_rate=0.1, momentum=0.9,
                                 weight_decay=1e-4))
    state = tr.init_state(seed=0)
    tokens = np.random.default_rng(0).integers(
        0, model.vocab_size, size=(dp * grad_accum, 129))
    x, y = tr.put_batch(*make_lm_batch(tokens))
    out: dict = {"dp": dp, "grad_accum": grad_accum,
                 "opt_sharding": sharding,
                 "n_params": int(sum(p.size for p in
                                     jax.tree.leaves(state.params)))}
    try:
        compiled = tr._train_step.lower(
            state.params, state.opt_state, x, y,
            *tr._extra_args(state)).compile()
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
    except Exception as e:  # noqa: BLE001 — record, don't die
        out["memory_analysis_error"] = f"{type(e).__name__}: {e}"
    return out


def measure_pp(dp: int, pp: int, num_micro: int, sharding: str,
               d_model: int = 128, vocab: int = 1024) -> dict:
    """Same claim under the 1F1B pipeline trainer (round-5): zero2
    reduce-scatters each tick's block-gradient contribution, so the
    scan-carry accumulator holds 1/dp f32 slices of the stacked block
    leaves (embed/head stay full until the post-scan scatter)."""
    import numpy as np

    from tpu_ddp.models.transformer import make_transformer
    from tpu_ddp.ops.optim import SGD
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import PipelineLMTrainer, make_lm_batch

    model = make_transformer("TransformerLM-tiny", max_seq_len=128,
                             num_layers=4, d_model=d_model,
                             d_ff=4 * d_model, vocab_size=vocab)
    mesh = make_mesh(jax.devices()[:dp * pp], dp=dp, pp=pp)
    tr = PipelineLMTrainer(model, mesh, num_micro=num_micro,
                           schedule="1f1b", opt_sharding=sharding,
                           optimizer=SGD(learning_rate=0.1, momentum=0.9,
                                         weight_decay=1e-4))
    state = tr.init_state(seed=0)
    tokens = np.random.default_rng(0).integers(
        0, model.vocab_size, size=(dp * num_micro, 129))
    x, y = tr.put_batch(*make_lm_batch(tokens))
    out: dict = {"dp": dp, "pp": pp, "num_micro": num_micro,
                 "opt_sharding": sharding,
                 "n_block_params": int(sum(
                     p.size for p in
                     jax.tree.leaves(state.params["blocks"])))}
    try:
        compiled = tr._train_step.lower(
            state.params, state.opt_state, x, y,
            *tr._extra_args(state)).compile()
        ma = compiled.memory_analysis()
        out["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
    except Exception as e:  # noqa: BLE001 — record, don't die
        out["memory_analysis_error"] = f"{type(e).__name__}: {e}"
    return out


def main() -> int:
    cells = []
    # Two model scales: the wide cell makes the parameter buffer the
    # dominant temp, so the zero2/zero1 ratio itself approaches the
    # activation floor + 1/dp; the tiny cell shows the exact accounting
    # (measured saving == 4*P*(1-1/dp) bytes) even when activations
    # dominate.
    for label, mkw in (("tiny (d_model 128, vocab 1k)", {}),
                       ("wide (d_model 512, vocab 16k)",
                        dict(d_model=512, vocab=16384))):
        for dp in (4, 8):
            for ga in (4, 8):
                pair: dict = {"model_cell": label}
                for sharding in ("zero1", "zero2"):
                    pair[sharding] = measure(dp, ga, sharding, **mkw)
                z1 = pair["zero1"].get("temp_bytes")
                z2 = pair["zero2"].get("temp_bytes")
                if z1 and z2:
                    n_p = pair["zero1"]["n_params"]
                    expect = 4.0 * n_p * (1.0 - 1.0 / dp)
                    pair["temp_ratio_zero2_over_zero1"] = round(z2 / z1, 4)
                    pair["measured_saving_bytes"] = z1 - z2
                    pair["expected_buffer_saving_bytes"] = int(expect)
                    pair["saving_vs_expected"] = round((z1 - z2) / expect,
                                                       4)
                cells.append(pair)
                print(f"[zero2-memory] {label} dp={dp} A={ga}: "
                      f"zero1={z1} zero2={z2} "
                      f"(expected saving {pair.get('expected_buffer_saving_bytes')})",
                      flush=True)
    pp_cells = []
    for label, mkw in (("tiny (d_model 128, vocab 1k)", {}),
                       ("wide (d_model 512, vocab 16k)",
                        dict(d_model=512, vocab=16384))):
        for dp, pp in ((4, 2), (2, 4)):
            for nm in (4, 8):
                pair: dict = {"model_cell": label}
                for sharding in ("zero1", "zero2"):
                    pair[sharding] = measure_pp(dp, pp, nm, sharding,
                                                **mkw)
                z1 = pair["zero1"].get("temp_bytes")
                z2 = pair["zero2"].get("temp_bytes")
                if z1 and z2:
                    # Stacked block leaves are pp-sharded, so the f32
                    # carry a stage holds is n_block/pp full-size under
                    # zero1 vs its 1/dp slice under zero2.
                    n_b = pair["zero1"]["n_block_params"] // pp
                    expect = 4.0 * n_b * (1.0 - 1.0 / dp)
                    pair["temp_ratio_zero2_over_zero1"] = round(z2 / z1, 4)
                    pair["measured_saving_bytes"] = z1 - z2
                    pair["expected_carry_saving_bytes"] = int(expect)
                    pair["saving_vs_expected"] = round((z1 - z2) / expect,
                                                       4)
                pp_cells.append(pair)
                print(f"[zero2-pp-memory] {label} dp={dp} pp={pp} "
                      f"M={nm}: zero1={z1} zero2={z2} (expected saving "
                      f"{pair.get('expected_carry_saving_bytes')})",
                      flush=True)
    out = {"model": "TransformerLM-tiny base (4L, seq 128) + wide cell",
           "note": "temp_bytes from XLA memory_analysis of the compiled "
                   "train step; zero2 scatters the f32 accumulation "
                   "buffer 1/dp (EXPERIMENTS.md methodology of the "
                   "pipeline-schedule table). expected_buffer_saving = "
                   "4*n_params*(1-1/dp) bytes (the f32 full-leaf buffer "
                   "shrinking to its dp slice)",
           "pp_note": "pipeline cells (round-5): 1F1B scan carry under "
                      "zero2 holds 1/dp slices of the stage's stacked "
                      "block gradients; expected_carry_saving = "
                      "4*(n_block_params/pp)*(1-1/dp) bytes",
           "cells": cells,
           "pp_cells": pp_cells}
    out_dir = REPO / "experiments"
    out_dir.mkdir(exist_ok=True)
    (out_dir / "zero2_memory.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""End-to-end LM walkthrough: raw text -> packed batches -> distributed
training -> sampling.

The complete LM story in one file (the text-side analogue of the CIFAR
ladder parts): byte-level tokenization and C++-packed training rows
(tpu_ddp/data/text.py), an LMTrainer over the local device mesh with
dropout + a warmup-cosine AdamW schedule, checkpointing, and greedy
sampling from the trained model.

Run anywhere (no downloads — the corpus is inline)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python examples/lm_text_train.py

Env knobs: TPU_DDP_LM_TEXT_EPOCHS (default 3), TPU_DDP_LM_TEXT_BATCH
(default 8), TPU_DDP_CKPT_DIR (optional checkpoint directory).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# A tiny self-contained corpus: structure the model can learn in a few
# epochs of byte-level training.
CORPUS = [
    "the quick brown fox jumps over the lazy dog. ",
    "pack my box with five dozen liquor jugs. ",
    "how vexingly quick daft zebras jump! ",
    "the five boxing wizards jump quickly. ",
] * 24


def main() -> int:
    import jax

    from tpu_ddp.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from tpu_ddp.data.text import (ByteTokenizer, epoch_batches,
                                   pack_documents)
    from tpu_ddp.models import make_transformer
    from tpu_ddp.models.generate import generate
    from tpu_ddp.ops.optim import AdamW, warmup_cosine
    from tpu_ddp.parallel.mesh import make_mesh
    from tpu_ddp.train.lm import LMTrainer

    epochs = int(os.environ.get("TPU_DDP_LM_TEXT_EPOCHS", "3"))
    batch = int(os.environ.get("TPU_DDP_LM_TEXT_BATCH", "8"))
    ckpt_dir = os.environ.get("TPU_DDP_CKPT_DIR")
    seq_len = 64

    tok = ByteTokenizer()
    rows = pack_documents(CORPUS, seq_len=seq_len)
    print(f"[lm_text] corpus: {len(CORPUS)} docs -> {rows.shape[0]} rows "
          f"of {seq_len + 1} tokens (vocab {tok.vocab_size})")

    if batch > rows.shape[0]:
        raise SystemExit(
            f"[lm_text] TPU_DDP_LM_TEXT_BATCH={batch} exceeds the "
            f"{rows.shape[0]} packed rows — every epoch would be empty "
            f"(drop_last); lower the batch or grow the corpus")
    model = make_transformer(
        "TransformerLM-tiny", vocab_size=tok.vocab_size,
        max_seq_len=seq_len, dropout_rate=0.05)
    mesh = make_mesh()
    # Schedule length = the steps that actually run (drop_last floors).
    steps_per_epoch = rows.shape[0] // batch
    total_steps = steps_per_epoch * epochs
    trainer = LMTrainer(
        model, mesh,
        optimizer=AdamW(learning_rate=warmup_cosine(
            3e-3, max(total_steps // 6, 1), max(total_steps, 2))))
    state = trainer.init_state(seed=0)
    print(f"[lm_text] {model.num_params(state.params):,} params on mesh "
          f"{dict(mesh.shape)}")

    for epoch in range(epochs):
        losses = []
        for inp, tgt in epoch_batches(rows, batch, seed=17, epoch=epoch):
            x, y = trainer.put_batch(inp, tgt)
            state, loss = trainer.train_step(state, x, y)
            losses.append(float(np.mean(np.asarray(loss))))
        print(f"[lm_text] epoch {epoch}: mean loss "
              f"{np.mean(losses):.4f} over {len(losses)} steps")
    if ckpt_dir:
        path = trainer.save_checkpoint(ckpt_dir, state)
        print(f"[lm_text] checkpoint: {path}")

    # Sample from the trained model: `model` is already dense (this mesh
    # has sp=tp=ep=1, and LMTrainer never mutates the caller's copy);
    # generate passes no rng, so dropout is inert at decode time.
    params = jax.device_get(state.params)
    prompt = tok.encode("the quick brown ")[None, :]
    out = generate(model, params, prompt, max_new_tokens=24)
    print(f"[lm_text] sample: {tok.decode(prompt[0])!r} -> "
          f"{tok.decode(np.asarray(out)[0])!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

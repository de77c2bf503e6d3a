"""The language model a configuration file describes, as the repo's own
``TransformerLM``, and the seeded token stream the training cells draw."""

from __future__ import annotations

import numpy as np


def build_model(config: dict, **overrides):
    """``TransformerLM`` at the sizes of a configuration file that uses
    the published ``config.json``'s own keys."""
    from tpu_ddp.models.transformer import TransformerLM

    return TransformerLM(
        name=config["name"], vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        **overrides)


def token_sampler(vocab: int, offset: float, rng):
    """``draw(shape)``: token ids with probability proportional to
    1 / (id + offset), a Zipf-like skew as text has."""
    cdf = np.cumsum(1.0 / (np.arange(vocab) + offset))
    cdf /= cdf[-1]
    return lambda shape: np.searchsorted(cdf, rng.random(shape)).astype(
        np.int32)

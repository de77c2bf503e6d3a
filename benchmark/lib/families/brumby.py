"""Family ``brumby`` (``model_type: brumby``): the repo's ``RetentionLM``
at the sizes of a configuration file that uses the published
``config.json``'s own keys (the Qwen3 block's). ``num_hidden_layers``
gives the layers that are run; the ``power_retention`` group gives what
the file does not: the degree, the gate, the normaliser's eps and the
state's dtype (each with its reason under ``assumed``)."""

from __future__ import annotations


def build(config: dict, **overrides):
    from tpu_ddp.models.retention import RetentionLM

    pr = config["power_retention"]
    if pr["degree"] != 2 or pr["gate"] != "per_kv_head" \
            or pr["state_dtype"] != "float32":
        raise ValueError("the model keeps a degree-2 float32 state with "
                         "one gate a K/V head")
    if config["tie_word_embeddings"] or config["attention_bias"]:
        raise ValueError("an untied head and no bias")
    return RetentionLM(
        name=config["name"], vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim_=config["head_dim"], d_model=config["hidden_size"],
        d_ff=config["intermediate_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"], retention_eps=pr["eps"],
        **overrides)


def reference_args(config: dict) -> dict:
    """What ``benchmark/reference/brumby.py``'s ``log_probs`` takes beside
    the parameters and the tokens."""
    return {"config": config}

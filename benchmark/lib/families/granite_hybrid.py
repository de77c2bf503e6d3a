"""Family ``granite_hybrid`` (``model_type: granitemoehybrid``): the
repo's ``HybridLM`` at the sizes of a configuration file that uses the
published ``config.json``'s own keys. ``num_hidden_layers`` and
``layer_types`` give the layers that are run; ``num_local_experts`` is the
number of experts HELD (``held_experts`` says which), the router keeps
the published width ``published.num_local_experts``."""

from __future__ import annotations


def build(config: dict, **overrides):
    from tpu_ddp.models.hybrid import HybridLM

    layers = tuple(config["layer_types"])
    if len(layers) != config["num_hidden_layers"]:
        raise ValueError("layer_types and num_hidden_layers disagree")
    lo, hi = config["held_experts"]
    if hi - lo != config["num_local_experts"]:
        raise ValueError("held_experts and num_local_experts disagree")
    if config["mamba_expand"] * config["hidden_size"] \
            != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not heads x head")
    return HybridLM(
        name=config["name"], vocab_size=config["vocab_size"],
        layer_types=layers,
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        d_model=config["hidden_size"], d_ff=config["intermediate_size"],
        shared_ff=config["shared_intermediate_size"],
        num_experts=config.get("published", {}).get(
            "num_local_experts", config["num_local_experts"]),
        top_k=config["num_experts_per_tok"], held=(lo, hi),
        ssm_heads=config["mamba_n_heads"],
        ssm_head_dim=config["mamba_d_head"],
        ssm_state=config["mamba_d_state"], ssm_conv=config["mamba_d_conv"],
        ssm_groups=config["mamba_n_groups"],
        ssm_chunk=config["mamba_chunk_size"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        logits_scaling=config["logits_scaling"],
        norm_eps=config["rms_norm_eps"], **overrides)


def reference_args(config: dict) -> dict:
    """What ``benchmark/reference/granite_hybrid.py``'s ``log_probs``
    takes beside the parameters and the tokens."""
    return {"config": config}

"""Family ``keye_vl2`` (``model_type: KeyeVL2``, its language model): the
repo's ``SparseMoeLM`` at the sizes of a configuration file that uses the
published ``config.json``'s own keys. ``num_hidden_layers`` gives the
layers that are run; ``num_local_experts`` is the number of experts HELD
(``held_experts`` says which), the router keeps the published width
``num_experts``; ``sa_config`` gives the indexer, ``index_rope_dim`` (an
assumption of the file's) the part of an index head that RoPE turns."""

from __future__ import annotations


def build(config: dict, **overrides):
    from tpu_ddp.models.sparse_moe import SparseMoeLM

    lo, hi = config["held_experts"]
    if hi - lo != config["num_local_experts"]:
        raise ValueError("held_experts and num_local_experts disagree")
    if config["decoder_sparse_step"] != 1 or config["mlp_only_layers"] \
            or not config["norm_topk_prob"]:
        raise ValueError("every layer must be an expert layer whose gates "
                         "are renormalised over the chosen experts")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the indexer has one shared key")
    return SparseMoeLM(
        name=config["name"], vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim_=config["head_dim"], d_model=config["hidden_size"],
        d_ff=config["moe_intermediate_size"],
        num_experts=config["num_experts"],
        top_k=config["num_experts_per_tok"], held=(lo, hi),
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"],
        index_rope_dim=config["index_rope_dim"], index_topk=sa["topk"],
        rope_theta=config["rope_theta"], norm_eps=config["rms_norm_eps"],
        **overrides)


def reference_args(config: dict) -> dict:
    """What ``benchmark/reference/keye_vl2.py``'s ``log_probs`` takes
    beside the parameters and the tokens."""
    return {"config": config}

"""Bytes that a decode step of a decoder with a learned sparse attention
(a lightning indexer and a top-k selection over the cached positions,
then an expert layer with no shared expert, in every layer) has to move,
from the configuration file alone (family ``keye_vl2``). Only bytes that
ANY implementation must move are counted: each weight matrix once, the
live index keys read once, the selected positions' K/V rows read once.
Temporaries, the index keys and K/V of positions past a slot's length,
and positions read more than once are not counted, so a share of the HBM
roofline built on these cannot pass 100% unless the time is wrong.

The configuration's keys are the published ``config.json``'s;
``num_hidden_layers`` is the layers that are run and
``num_local_experts`` the experts HELD (``published`` has the model's own
counts). Weights, K/V and index keys in 2 bytes (the file's
``assumed``).
"""

from __future__ import annotations

WEIGHT = CACHE = 2      # bytes a value


def attn_params(cfg: dict) -> int:
    """q, k, v and the output projection, and the per-head q / k norms."""
    dm, h, kvh, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    return dm * (h + 2 * kvh) * hd + h * hd * dm + 2 * hd


def index_params(cfg: dict) -> int:
    """The indexer: its queries, its one key, its head weights, and the
    key's LayerNorm."""
    sa = cfg["sa_config"]
    dm, j, di = (cfg["hidden_size"], sa["indexer_num_heads"],
                 sa["indexer_head_dim"])
    return dm * j * di + dm * di + dm * j + 2 * di


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def experts_params(cfg: dict) -> int:
    """The held experts' gated MLPs."""
    return (cfg["num_local_experts"] * 3 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def layer_params(cfg: dict) -> int:
    """One layer as held here, its two input norms included."""
    return (attn_params(cfg) + index_params(cfg) + router_params(cfg)
            + experts_params(cfg) + 2 * cfg["hidden_size"])


def weight_bytes(cfg: dict) -> int:
    """All the weights held: the layers, the embedding, the untied head
    and the final norm."""
    dm = cfg["hidden_size"]
    return WEIGHT * (cfg["num_hidden_layers"] * layer_params(cfg)
                     + 2 * cfg["vocab_size"] * dm + dm)


def kv_bytes_per_position(cfg: dict) -> int:
    """K and V of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * CACHE


def index_bytes_per_position(cfg: dict) -> int:
    """The index key of one position in one layer."""
    return cfg["sa_config"]["indexer_head_dim"] * CACHE


def pool_bytes(cfg: dict) -> int:
    """The engine's default pool: slots x max_seq_len / block_size + 1
    blocks of K/V and index keys in every layer."""
    geo = cfg["serve"]
    blocks = geo["num_slots"] * (geo["max_seq_len"] // geo["block_size"]) + 1
    return (cfg["num_hidden_layers"] * blocks * geo["block_size"]
            * (kv_bytes_per_position(cfg) + index_bytes_per_position(cfg)))


def sparse_attn_step_bytes(cfg: dict, index_tokens: float,
                           selected_tokens: float) -> float:
    """The sparse part of every layer in one decode step: the indexer's
    weights once, the index keys of the ``index_tokens`` live positions
    (summed over the rows) once, the K/V rows of the ``selected_tokens``
    selected positions once."""
    return cfg["num_hidden_layers"] * (
        index_params(cfg) * WEIGHT
        + index_tokens * index_bytes_per_position(cfg)
        + selected_tokens * kv_bytes_per_position(cfg))

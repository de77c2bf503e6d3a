"""Bytes and operations of a decoder whose every layer is gated
symmetric-power retention of degree 2 (family ``brumby``), from the
configuration file alone. The state a layer and sequence is ``S`` (KV, F,
hd) and ``z`` (KV, F) in float32, ``F = hd (hd + 1) / 2`` (the file's
``assumed``); weights in 2 bytes. Only bytes that ANY implementation must
move are counted where a roofline is built on them
(:func:`state_read_bytes`), so a share of the HBM roofline built on these
cannot pass 100% unless the time is wrong.

The configuration's keys are the published ``config.json``'s (the Qwen3
block's); ``num_hidden_layers`` is the layers that are run
(``published`` has the model's own count).
"""

from __future__ import annotations

WEIGHT = 2      # bytes a weight (bf16)
STATE = 4       # bytes a value of the state (float32)


def features(cfg: dict) -> int:
    """The symmetric power's width: 8,256 at a head of 128."""
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2


def layer_params(cfg: dict) -> int:
    """One layer: q, k, v, the output projection, the gate (a weight
    column and a bias a K/V head), the SwiGLU MLP, the per-head q / k
    norms and the two input norms."""
    dm, h, kvh, hd, ff = (cfg["hidden_size"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"],
                          cfg["intermediate_size"])
    return (dm * (h + 2 * kvh) * hd + h * hd * dm + (dm + 1) * kvh
            + 3 * dm * ff + 2 * hd + 2 * dm)


def weight_bytes(cfg: dict) -> int:
    """All the weights held: the layers, the embedding, the untied head
    and the final norm."""
    dm = cfg["hidden_size"]
    return WEIGHT * (cfg["num_hidden_layers"] * layer_params(cfg)
                     + 2 * cfg["vocab_size"] * dm + dm)


def state_bytes(cfg: dict) -> int:
    """``S`` and ``z`` of one layer and one sequence: 34.08 MB at
    Brumby-14B's widths."""
    return (STATE * cfg["num_key_value_heads"] * features(cfg)
            * (cfg["head_dim"] + 1))


def state_pool_bytes(cfg: dict) -> int:
    """The engine's state pool: every layer's state for every slot."""
    return (cfg["num_hidden_layers"] * cfg["serve"]["num_slots"]
            * state_bytes(cfg))


def kv_bytes_per_position(cfg: dict) -> int:
    """What a K/V cache of the same layer would hold a position (bf16),
    for the comparison: the state equals it at ``state_bytes /
    kv_bytes_per_position`` positions."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * WEIGHT


def state_read_bytes(cfg: dict, rows: float) -> float:
    """The least a decode step of ``rows`` sequences must move of the
    state: each row's ``S`` and ``z`` in every layer, read once. Not the
    write: a form that folds the last few positions' ``k, v`` into the
    state every few steps need not write it each step."""
    return cfg["num_hidden_layers"] * rows * state_bytes(cfg)


def decode_step_bytes(cfg: dict, rows: float) -> float:
    """What the per-token form moves in a decode step of ``rows``
    sequences: every weight but the embedding (one row a sequence) once,
    and the state read and written once."""
    dm = cfg["hidden_size"]
    return (weight_bytes(cfg) - WEIGHT * cfg["vocab_size"] * dm
            + 2 * state_read_bytes(cfg, rows))


def prefill_flops(cfg: dict, chunk: int) -> dict:
    """Operations of a prefill chunk of ``chunk`` positions in one layer:
    the projections and the MLP (``matmul``), the incoming state read by
    every query (``carry``: phi(Q)^T S0 and phi(Q)^T z0), the state's
    update (``update``), and the quadratic part within the chunk
    (``chunk``: the whole square of scores and weighted values)."""
    h, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f = features(cfg)
    dm = cfg["hidden_size"]
    return {
        "matmul": 2 * chunk * (layer_params(cfg) - 2 * hd - 2 * dm - kvh),
        "carry": 2 * chunk * h * f * (hd + 1),
        "update": 2 * chunk * kvh * f * (hd + 1),
        "chunk": 2 * 2 * chunk * chunk * h * hd,
    }

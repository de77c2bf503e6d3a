"""Bytes that a step of a hybrid decoder (Mamba-2 and attention mixers,
an expert layer plus a shared gated MLP in every layer) has to move, from
the configuration file alone. Only bytes that ANY implementation must
move are counted: each weight matrix once, the recurrent state of the
slots the step advances read and written once, the live K/V read once.
Temporaries, the gathered rows of the expert layer, and the state of
slots that ride along untouched are not counted, so a share of the HBM
roofline built on these cannot pass 100% unless the time is wrong.

The configuration's keys are the published ``config.json``'s;
``num_hidden_layers`` / ``layer_types`` are the layers that are run and
``num_local_experts`` the experts HELD (``published`` has the model's
own counts). Weights and K/V in 2 bytes, the recurrent state in 4 and the
convolution tail in 2 (the file's ``assumed``).
"""

from __future__ import annotations

WEIGHT, STATE, TAIL, KV = 2, 4, 2, 2    # bytes a value


def layers(cfg: dict, kind: str) -> int:
    return sum(t == kind for t in cfg["layer_types"])


def _d_inner(cfg: dict) -> int:
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"]


def _conv_dim(cfg: dict) -> int:
    return _d_inner(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def ssm_mixer_params(cfg: dict) -> int:
    """One Mamba-2 mixer: in_proj, out_proj, convolution and its bias,
    dt_bias, A_log, D, the gated norm and the layer's input norm."""
    dm, di, cdim = cfg["hidden_size"], _d_inner(cfg), _conv_dim(cfg)
    heads = cfg["mamba_n_heads"]
    return (dm * (di + cdim + heads) + di * dm
            + (cfg["mamba_d_conv"] + 1) * cdim + 3 * heads + di + dm)


def attn_mixer_params(cfg: dict) -> int:
    dm, h, kvh = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    hd = dm // h
    return dm * (h + 2 * kvh) * hd + h * hd * dm + dm


def moe_params(cfg: dict) -> int:
    """One layer's MLP half as held here: the router at its published
    width, the shared gated MLP, the held experts, the norm."""
    dm, ff, sff = (cfg["hidden_size"], cfg["intermediate_size"],
                   cfg["shared_intermediate_size"])
    routed = cfg.get("published", {}).get("num_local_experts",
                                          cfg["num_local_experts"])
    return (dm * routed + 3 * dm * sff
            + cfg["num_local_experts"] * 3 * dm * ff + dm)


def state_bytes_per_slot(cfg: dict) -> int:
    """One sequence's recurrent state over all state layers."""
    per_layer = (_d_inner(cfg) * cfg["mamba_d_state"] * STATE
                 + (cfg["mamba_d_conv"] - 1) * _conv_dim(cfg) * TAIL)
    return layers(cfg, "mamba") * per_layer


def kv_bytes_per_token(cfg: dict) -> int:
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * layers(cfg, "attention") * cfg["num_key_value_heads"] * hd
            * KV)


def ssm_step_bytes(cfg: dict, state_slots: float) -> float:
    """The state layers' mixers in one decode step: their weights once,
    the state of the advanced slots read and written once."""
    return (layers(cfg, "mamba") * ssm_mixer_params(cfg) * WEIGHT
            + 2 * state_slots * state_bytes_per_slot(cfg))


def moe_step_bytes(cfg: dict) -> int:
    """Every layer's MLP half in one decode step: at some tens of
    assignments an expert, every held expert is read."""
    return cfg["num_hidden_layers"] * moe_params(cfg) * WEIGHT


def weight_bytes(cfg: dict) -> int:
    """All the weights held, the tied embedding once (the head reads all
    of it; the lookup's few rows are not counted again)."""
    params = (layers(cfg, "mamba") * ssm_mixer_params(cfg)
              + layers(cfg, "attention") * attn_mixer_params(cfg)
              + cfg["num_hidden_layers"] * moe_params(cfg)
              + cfg["vocab_size"] * cfg["hidden_size"]
              + cfg["hidden_size"])
    return params * WEIGHT


def decode_step_bytes(cfg: dict, state_slots: float,
                      context_tokens: float) -> float:
    """A whole-bank decode step: the weights once, the state of the
    advanced slots read and written, the live K/V read."""
    return (weight_bytes(cfg)
            + 2 * state_slots * state_bytes_per_slot(cfg)
            + context_tokens * kv_bytes_per_token(cfg))

"""Bytes a decode step of the Ling configuration has to move, from the
configuration file's numbers alone (no program code), for
`decode_hbm_roofline`.

A step reads every weight outside the routed experts once (the embedding
only at the rows of its tokens), each *touched* held expert once, each active
slot's recurrent state and convolution tail once and writes them once, and
reads the live latents once. Activations (64 rows) are left out: they are
under a thousandth of the rest.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib import reference_ling

ITEM = {"bfloat16": 2, "float32": 4}


def kinds(cfg: Dict[str, Any]):
    return reference_ling.layer_kinds(
        {**cfg, "layer_ids": cfg["program"]["layer_ids"]})


def block_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of each kind of block and of one expert."""
    D, H, dk = cfg["hidden_size"], cfg["num_attention_heads"], cfg["head_dim"]
    K = cfg["short_conv_kernel_size"]
    rank, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    F, Fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    E = cfg["program"]["router_num_experts"]
    return {
        # q, k, v, W_a, W_o; beta and gate; conv; head norm
        "kda": 5 * D * H * dk + 2 * D * H + 3 * K * H * dk + dk,
        "kda_f32": H + H * dk,               # A_log, dt_bias
        "mla": (D * H * (nope + rope) + D * (rank + rope) + rank
                + rank * H * (nope + dv) + D * H + H * dv * D),
        "dense": 3 * D * F,
        "router": D * E + E,                 # float32
        "shared": 3 * D * Fe,
        "expert": 3 * D * Fe,
        "norms": 2 * D,
    }


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Bytes of the weights a decode step reads whatever it routes
    (`fixed`), of one expert (`expert`), and of everything held (`held`)."""
    b = block_params(cfg)
    item = ITEM[cfg["program"]["param_dtype"]]
    fixed = 0.0
    n_moe = 0
    for attn, ffn in kinds(cfg):
        fixed += (b[attn] + b["norms"]) * item
        if attn == "kda":
            fixed += b["kda_f32"] * 4
        if ffn == "dense":
            fixed += b["dense"] * item
        else:
            fixed += b["shared"] * item + b["router"] * 4
            n_moe += 1
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    fixed += (D + D * V) * item                  # final norm, head
    expert = b["expert"] * item
    return {"fixed": fixed, "expert": float(expert), "moe_layers": n_moe,
            "held": fixed + V * D * item
            + n_moe * cfg["num_experts"] * expert}


def decode_step_bytes(cfg: Dict[str, Any], active_slots: float,
                      experts_touched: float, latent_positions: float
                      ) -> Dict[str, float]:
    """Bytes one decode step must move. `active_slots`: slots with a
    sequence; `experts_touched`: held experts with a row, summed over the
    expert layers; `latent_positions`: live positions read, summed over the
    slots and the latent-attention layers."""
    w = weight_bytes(cfg)
    H, dk = cfg["num_attention_heads"], cfg["head_dim"]
    n_kda = sum(a == "kda" for a, _ in kinds(cfg))
    act = ITEM[cfg["program"]["dtype"]]
    state = n_kda * H * dk * dk * 4                         # float32, a slot
    tail = n_kda * (cfg["short_conv_kernel_size"] - 1) * 3 * H * dk * act
    parts = {
        "weights": w["fixed"] + active_slots * cfg["hidden_size"] * act,
        "experts": experts_touched * w["expert"],
        "state": 2.0 * active_slots * (state + tail),
        "latents": latent_positions
        * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * act,
    }
    parts["total"] = sum(parts.values())
    return parts

"""Bytes and operations a step of the JoyAI-LLM-Flash configuration has to
move and to do, from the configuration file's numbers and the engine's
counters alone (no program code), for `joyai_latent_attention_roofline`,
`joyai_step_hbm_roofline` and `joyai_step_mfu`.

Bytes: a step reads every weight outside the routed experts once (the
embedding only at the rows of its tokens) and each *touched* held expert
once; its decode rows read the live latents of their slots once a layer
(`latent_positions_read`, summed over the layers), and a chunk the latents
its rows see (`chunk_latents_read`). A latent is one row of the pool, 1,280 B
with the lanes' padding, and counts once whatever the program reads: the
decode kernel is handed the pool as keys and as values. Activations and the
rows' own latents' writes are left out.

Operations: the model's, for the rows the steps really ran (chunk rows and
decode rows, no padding): two a weight of every matmul outside the experts a
row, of one expert a routed pair on a held expert, of the head a row whose
logits are read; the attention at the *expanded* form's count, 4 x heads x
(nope + rope + v) / 2 a query-key pair, whatever form the program runs: a
decode row's pairs are its live latents, a chunk's rows' are counted from the
positions it covered and its own width.
"""

from __future__ import annotations

from typing import Any, Dict

ITEM = {"bfloat16": 2, "float32": 4}
COUNTERS = ("steps", "steps_with_chunk", "prefill_chunk_tokens",
            "moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "latent_positions_read", "chunk_latents_read")


def block_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of a layer's attention (`attn`), its norms, the router (in
    float32), the shared expert, one routed expert and the dense FFN."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    F = cfg["moe_intermediate_size"]
    return {"attn": D * qr + qr * H * (nope + rope) + D * (kr + rope)
            + kr * H * (nope + v) + H * v * D,
            "norms": 2 * D + qr + kr,
            "router": D * cfg["router_num_experts"],
            "shared": 3 * D * F * cfg["n_shared_experts"],
            "expert": 3 * D * F,
            "dense": 3 * D * cfg["intermediate_size"]}


def layer_counts(cfg: Dict[str, Any]):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Bytes of the weights a step reads whatever it routes (`fixed`), of one
    expert (`expert`), and of everything held (`held`, with `params` the
    count)."""
    b = block_params(cfg)
    dense, moe = layer_counts(cfg)
    item = ITEM[cfg["program"]["param_dtype"]]
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    fixed = (dense + moe) * (b["attn"] + b["norms"]) * item
    fixed += dense * b["dense"] * item
    fixed += moe * (b["shared"] * item + b["router"] * 4)
    fixed += (D + D * V) * item                  # final norm, head
    held_experts = moe * cfg["n_routed_experts"] * b["expert"]
    params = ((dense + moe) * (b["attn"] + b["norms"]) + dense * b["dense"]
              + moe * (b["shared"] + b["router"] + cfg["router_num_experts"])
              + D + 2 * D * V + held_experts)
    return {"fixed": float(fixed), "expert": float(b["expert"] * item),
            "held": fixed + V * D * item + held_experts * item
            + moe * cfg["router_num_experts"] * 4,
            "params": params}


def position_bytes(cfg: Dict[str, Any]) -> int:
    """A position's latent in one layer, as the pool keeps it: rank + rope
    values rounded up to whole lanes of 128."""
    width = -(-(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) // 128) * 128
    return width * ITEM[cfg["program"]["dtype"]]


def rows_of(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """From the counters' changes `d`: the rows the steps ran (`rows`), of
    them the chunks' (`chunk_rows`) and the decode rows (`decode_rows`)."""
    rows = d["moe_pairs_routed"] / (
        cfg["num_experts_per_tok"] * layer_counts(cfg)[1])
    return {"rows": rows, "chunk_rows": d["prefill_chunk_tokens"],
            "decode_rows": rows - d["prefill_chunk_tokens"]}


def decode_attention_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> float:
    """What the decode rows' attention (the paged kernel's calls) must read:
    each live latent once."""
    return d["latent_positions_read"] * float(position_bytes(cfg))


def step_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """Bytes the steps counted in `d` (the changes of `COUNTERS` over a
    window) must move, all of them together."""
    w, r = weight_bytes(cfg), rows_of(cfg, d)
    act = ITEM[cfg["program"]["dtype"]]
    parts = {
        "weights": d["steps"] * w["fixed"] + r["rows"] * cfg["hidden_size"] * act,
        "experts": d["moe_experts_touched"] * w["expert"],
        "latents": decode_attention_bytes(cfg, d)
        + d["chunk_latents_read"] * position_bytes(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


def chunk_pairs(cfg: Dict[str, Any], d: Dict[str, float]) -> float:
    """Query-key pairs of the chunks' rows, summed over the layers: a chunk
    of n rows that ends at position e scores n e - n (n - 1) / 2 pairs a
    layer. From the sums alone, with every chunk taken at the mean width n =
    rows / chunks: n x (positions covered) less the mean triangle."""
    chunks = d["steps_with_chunk"]
    if not chunks:
        return 0.0
    n = d["prefill_chunk_tokens"] / chunks
    layers = cfg["num_hidden_layers"]
    return n * d["chunk_latents_read"] - layers * chunks * n * (n - 1) / 2.0


def step_flops(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """The model's operations of the rows the steps counted in `d` ran."""
    b, r = block_params(cfg), rows_of(cfg, d)
    dense, moe = layer_counts(cfg)
    pair = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    parts = {
        "matmuls": 2.0 * r["rows"] * (
            (dense + moe) * b["attn"] + dense * b["dense"]
            + moe * (b["router"] + b["shared"])),
        "experts": 2.0 * d["moe_pairs_held"] * b["expert"],
        # a decode row's logits, and one row's a chunk
        "head": 2.0 * (r["decode_rows"] + d["steps_with_chunk"])
        * cfg["hidden_size"] * cfg["vocab_size"],
        "attention": pair * (d["latent_positions_read"] + chunk_pairs(cfg, d)),
    }
    parts["total"] = sum(parts.values())
    return parts


def window_counters(art: Dict[str, Any]):
    """The changes of `COUNTERS` over the run's window, or None where the
    program reports none of them (any parent of the PR that added them)."""
    a, b = art.get("stats_open"), art.get("stats_close")
    if not a or not b or any(k not in a or k not in b for k in COUNTERS):
        return None
    d = {k: float(b[k] - a[k]) for k in COUNTERS}
    return d if d["steps"] > 0 else None

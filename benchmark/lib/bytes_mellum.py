"""Bytes and operations a step of the Mellum 2 configuration has to move and
to do, from the configuration file's numbers and the engine's counters alone
(no program code), for `mellum_decode_attention_roofline`,
`mellum_step_hbm_roofline` and `mellum_step_mfu`.

Bytes: a step reads every weight outside the experts once (the embedding
only at the rows of its tokens) and each *touched* expert once; its decode
rows read the live keys and values of their slots once, in a full layer all
of them and in a window layer the window's (`kv_positions_live`,
`window_positions`: both are summed over the layers of their kind), and a
chunk those its rows see (`chunk_keys_read`). Activations and the rows' own
keys' writes are left out: 304 rows of 2,304 are under a thousandth of the
rest.

Operations: the model's, for the rows the steps really ran (chunk rows and
decode rows, no padding): two a weight of every matmul outside the experts a
row, of one expert a routed pair, of the head a row whose logits are read;
the attention's scores and weighted sums over the positions each row sees.
"""

from __future__ import annotations

from typing import Any, Dict

ITEM = {"bfloat16": 2, "float32": 4}
COUNTERS = ("steps", "steps_with_chunk", "prefill_chunk_tokens",
            "moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "kv_positions_live", "window_positions", "chunk_keys_read",
            "chunk_pairs", "attn_positions_live")


def block_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of a layer outside its experts (`attn` those a row
    multiplies, `norms`, `router` in float32) and of one expert."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    nq = cfg["num_attention_heads"] * hd
    nkv = cfg["num_key_value_heads"] * hd
    return {"attn": D * (nq + 2 * nkv) + nq * D, "norms": 2 * D + 2 * hd,
            "router": D * cfg["num_experts"],
            "expert": 3 * D * cfg["moe_intermediate_size"]}


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Bytes of the weights a step reads whatever it routes (`fixed`), of one
    expert (`expert`), and of everything held (`held`)."""
    b, L = block_params(cfg), cfg["num_hidden_layers"]
    item = ITEM[cfg["program"]["param_dtype"]]
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    fixed = L * ((b["attn"] + b["norms"]) * item + b["router"] * 4)
    fixed += (D + D * V) * item                  # final norm, head
    expert = float(b["expert"] * item)
    return {"fixed": float(fixed), "expert": expert,
            "held": fixed + V * D * item + L * cfg["num_experts"] * expert}


def position_bytes(cfg: Dict[str, Any]) -> int:
    """A position's keys and values in one layer."""
    return (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
            * ITEM[cfg["program"]["dtype"]])


def rows_of(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """From the counters' changes `d`: the rows the steps ran (`rows`), of
    them the chunks' (`chunk_rows`) and the decode rows (`decode_rows`)."""
    rows = d["moe_pairs_routed"] / (
        cfg["num_experts_per_tok"] * cfg["num_hidden_layers"])
    return {"rows": rows, "chunk_rows": d["prefill_chunk_tokens"],
            "decode_rows": rows - d["prefill_chunk_tokens"]}


def decode_attention_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> float:
    """What the decode rows' attention (the paged kernel's calls, both kinds
    of layer) must read: the live positions, and in a window layer only the
    window's."""
    return (d["kv_positions_live"] + d["window_positions"]) * float(
        position_bytes(cfg))


def step_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """Bytes the steps counted in `d` (the changes of `COUNTERS` over a
    window) must move, all of them together."""
    w, r = weight_bytes(cfg), rows_of(cfg, d)
    act = ITEM[cfg["program"]["dtype"]]
    parts = {
        "weights": d["steps"] * w["fixed"] + r["rows"] * cfg["hidden_size"] * act,
        "experts": d["moe_experts_touched"] * w["expert"],
        "kv": decode_attention_bytes(cfg, d)
        + d["chunk_keys_read"] * position_bytes(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


def step_flops(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """The model's operations of the rows the steps counted in `d` ran."""
    b, r = block_params(cfg), rows_of(cfg, d)
    pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"]   # q.k and p.v
    parts = {
        "matmuls": 2.0 * r["rows"] * cfg["num_hidden_layers"]
        * (b["attn"] + b["router"]),
        "experts": 2.0 * d["moe_pairs_held"] * b["expert"],
        # a decode row's logits, and one row's a chunk
        "head": 2.0 * (r["decode_rows"] + d["steps_with_chunk"])
        * cfg["hidden_size"] * cfg["vocab_size"],
        "attention": pair * (d["kv_positions_live"] + d["window_positions"]
                             + d["chunk_pairs"]),
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

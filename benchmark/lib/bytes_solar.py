"""Bytes and operations a step of the Solar-Open2 configuration has to move
and to do, from the configuration file's numbers and the engine's counters
alone (no program code), for `solar_step_hbm_roofline` and `solar_step_mfu`.

Bytes: a step reads every weight outside the routed experts once (the
embedding only at the rows of its tokens), each *touched* held expert once,
each decoding slot's recurrent state and convolution tail once and writes
them once, as does a chunk for its slot; it reads the live keys and values
of the decode rows' slots once and, for a chunk, those of its sequence up to
the chunk's end once; a snapshot taken or restored moves one snapshot's
bytes. Activations are left out: 272 rows of 4,096 are under a thousandth of
the rest.

Operations: the model's, for the rows the steps really ran (chunk rows and
decode rows, no padding): two a weight of every matmul outside the routed
experts a row, of one expert a held pair, of the head a row whose logits
are read; the softmax layers' scores and weighted sums over the positions
each row attends; the delta rule's four passes over a head's state a row.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.lib import reference_solar

ITEM = {"bfloat16": 2, "float32": 4}


def kinds(cfg: Dict[str, Any]):
    return reference_solar.layer_kinds(
        {**cfg, "layer_ids": cfg["program"]["layer_ids"]})


def block_params(cfg: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of each kind of block and of one expert; `*_matmul`: those
    of them that a row multiplies."""
    D, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    H, dk, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    r = cfg["program"]["kda_gate_rank"]
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    F, E = cfg["moe_intermediate_size"], cfg["program"]["router_num_experts"]
    kda_matmul = 4 * D * H * dk + 2 * (D * r + r * H * dk) + D * H
    gqa_matmul = D * (nq + 2 * nkv) + 2 * D * nq     # q k v, the gate, o
    return {
        "kda": kda_matmul + 3 * K * H * dk + dk, "kda_matmul": kda_matmul,
        "kda_f32": H + H * dk,                 # A_log, dt_bias
        "gqa": gqa_matmul, "gqa_matmul": gqa_matmul,
        "router": D * E + E, "router_matmul": D * E,      # float32
        "shared": 3 * D * F, "expert": 3 * D * F, "norms": 2 * D,
    }


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """Bytes of the weights a step reads whatever it routes (`fixed`), of one
    expert (`expert`), and of everything held (`held`)."""
    b = block_params(cfg)
    item = ITEM[cfg["program"]["param_dtype"]]
    fixed = 0.0
    for attn in kinds(cfg):
        fixed += (b[attn] + b["norms"] + b["shared"]) * item + b["router"] * 4
        if attn == "kda":
            fixed += b["kda_f32"] * 4
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    fixed += (D + D * V) * item                  # final norm, head
    expert = b["expert"] * item
    return {"fixed": fixed, "expert": float(expert),
            "held": fixed + V * D * item
            + len(kinds(cfg)) * cfg["n_routed_experts"] * expert}


def slot_bytes(cfg: Dict[str, Any]) -> float:
    """What one slot carries beside its blocks, which is also one snapshot:
    every KDA layer's state (float32) and convolution tail."""
    lin = cfg["linear_attn_config"]
    H, dk = lin["num_heads"], lin["head_dim"]
    n_kda = sum(a == "kda" for a in kinds(cfg))
    act = ITEM[cfg["program"]["dtype"]]
    return n_kda * (H * dk * dk * 4
                    + (lin["short_conv_kernel_size"] - 1) * 3 * H * dk * act)


def rows_of(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """From the counters' changes `d`: the rows the steps ran (`rows`), of
    them the chunks' (`chunk_rows`) and the decode rows (`decode_rows`)."""
    rows = d["moe_pairs_routed"] / (cfg["num_experts_per_tok"] * len(kinds(cfg)))
    return {"rows": rows, "chunk_rows": d["prefill_chunk_tokens"],
            "decode_rows": rows - d["prefill_chunk_tokens"]}


def step_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """Bytes the steps counted in `d` (the changes of the engine's counters
    over a window: `steps`, `steps_with_chunk`, `prefill_chunk_tokens`,
    `moe_pairs_routed`, `moe_experts_touched`, `kv_positions_live`,
    `chunk_positions_live`, `snapshots_taken`, `snapshots_restored`) must
    move, all of them together."""
    w, r = weight_bytes(cfg), rows_of(cfg, d)
    act = ITEM[cfg["program"]["dtype"]]
    n_gqa = sum(a == "gqa" for a in kinds(cfg))
    position = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * act
    parts = {
        "weights": d["steps"] * w["fixed"] + r["rows"] * cfg["hidden_size"] * act,
        "experts": d["moe_experts_touched"] * w["expert"],
        "state": 2.0 * (r["decode_rows"] + d["steps_with_chunk"])
        * slot_bytes(cfg),
        "kv": (d["kv_positions_live"] + d["chunk_positions_live"] * n_gqa)
        * position,
        "snapshots": (d["snapshots_taken"] + d["snapshots_restored"])
        * slot_bytes(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


def step_flops(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """The model's operations of the rows the steps counted in `d` ran
    (`step_bytes`' counters and `moe_pairs_held`, `attn_positions_live`,
    `chunk_attn_pairs`)."""
    b, r = block_params(cfg), rows_of(cfg, d)
    lin = cfg["linear_attn_config"]
    ks = kinds(cfg)
    n_kda, n_gqa = ks.count("kda"), ks.count("gqa")
    per_row = (n_kda * b["kda_matmul"] + n_gqa * b["gqa_matmul"]
               + len(ks) * (b["router_matmul"] + b["shared"]))
    pair = 4 * cfg["num_attention_heads"] * cfg["head_dim"]   # q.k and p.v
    parts = {
        "matmuls": 2.0 * r["rows"] * per_row,
        "experts": 2.0 * d["moe_pairs_held"] * b["expert"],
        # a decode row's logits, and one row's a chunk
        "head": 2.0 * (r["decode_rows"] + d["steps_with_chunk"])
        * cfg["hidden_size"] * cfg["vocab_size"],
        "attention": pair * n_gqa * (d["attn_positions_live"]
                                     + d["chunk_attn_pairs"]),
        # decay, S^T k, the rank-one update, S^T q: 7 a state element a row
        "recurrence": 7.0 * r["rows"] * n_kda * lin["num_heads"]
        * lin["head_dim"] ** 2,
    }
    parts["total"] = sum(parts.values())
    return parts


COUNTERS = ("steps", "steps_with_chunk", "prefill_chunk_tokens",
            "moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "kv_positions_live", "attn_positions_live", "chunk_positions_live",
            "chunk_attn_pairs", "snapshots_taken", "snapshots_restored")


def window_counters(art: Dict[str, Any]):
    """The changes of `COUNTERS` over the run's window, or None where the
    program reports none of them (any parent of the PR that added them)."""
    a, b = art.get("stats_open"), art.get("stats_close")
    if not a or not b or any(k not in a or k not in b for k in COUNTERS):
        return None
    d = {k: float(b[k] - a[k]) for k in COUNTERS}
    return d if d["steps"] > 0 else None

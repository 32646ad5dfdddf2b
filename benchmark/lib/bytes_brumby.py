"""Bytes and operations a step of the Brumby configuration has to move and to
do, from the configuration file's numbers and the engine's counters alone (no
program code), for `retention_step_hbm_roofline`, `brumby_step_hbm_roofline`
and `brumby_step_mfu`.

The state is counted as the mechanism needs it, whatever the program holds: a
KV head's state over the symmetric square of the key, `hd (hd + 1) / 2 =
8,256` rows of `hd` float32 values, and a normaliser of 8,256. A padded
layout then reads a lower share of the roofline, as it should.

Bytes: a step reads every layer's weights, the final norm and the head once
and the embedding at the rows of its tokens; each decoding slot's state is
read once and written once, as is a chunk's slot's; a snapshot taken or
restored moves one slot's bytes once more. Activations are left out.

Operations: the model's, for the rows the steps really ran (chunk rows and
decode rows, no padding): two a weight of every matmul a row, of the head a
row whose logits are read; the retention as the recurrence does it, per row
and layer `2 x 8,256 x hd` a query head (the state contracted with phi(q))
and as much a KV head (the rank-one update).
"""

from __future__ import annotations

from typing import Any, Dict

ITEM = {"bfloat16": 2, "float32": 4}
COUNTERS = ("steps", "steps_with_chunk", "prefill_chunk_tokens",
            "rows_decoded", "snapshots_taken", "snapshots_restored")


def phi_rows(cfg: Dict[str, Any]) -> int:
    """The symmetric square of a key of `head_dim` channels."""
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    """The weights a row multiplies in one layer: q, k, v, the gate, o, and
    the SwiGLU's three."""
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    nq = cfg["num_attention_heads"] * hd
    nkv = cfg["num_key_value_heads"] * hd
    return (D * (nq + 2 * nkv) + D * cfg["num_key_value_heads"] + nq * D
            + 3 * D * cfg["intermediate_size"])


def weight_bytes(cfg: Dict[str, Any]) -> Dict[str, float]:
    """`step`: what a step reads whatever its rows (layers, final norm,
    head); `held`: everything resident (with the embedding)."""
    item = ITEM[cfg["program"]["param_dtype"]]
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    hd, KV = cfg["head_dim"], cfg["num_key_value_heads"]
    layer = (layer_matmul_params(cfg) + 2 * D + 2 * hd) * item + KV * 4
    step = L * layer + (D + D * V) * item
    return {"layer": float(layer), "step": float(step),
            "held": float(step + V * D * item)}


def slot_bytes(cfg: Dict[str, Any]) -> float:
    """What one slot carries, which is also one snapshot: every layer's
    state and normaliser, float32."""
    return float(cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
                 * (phi_rows(cfg) * cfg["head_dim"] + phi_rows(cfg)) * 4)


def retention_step_bytes(cfg: Dict[str, Any], rows_decoded: float) -> float:
    """What the decode kernel must move for `rows_decoded` rows (a row is
    one slot in one step, through every layer): the slot's state read once
    and written once."""
    return 2.0 * rows_decoded * slot_bytes(cfg)


def step_bytes(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """Bytes the steps counted in `d` (the changes of `COUNTERS` over a
    window) must move, all of them together."""
    act = ITEM[cfg["program"]["dtype"]]
    rows = d["rows_decoded"] + d["prefill_chunk_tokens"]
    parts = {
        "weights": d["steps"] * weight_bytes(cfg)["step"]
        + rows * cfg["hidden_size"] * act,
        "state": retention_step_bytes(
            cfg, d["rows_decoded"] + d["steps_with_chunk"]),
        "snapshots": (d["snapshots_taken"] + d["snapshots_restored"])
        * slot_bytes(cfg),
    }
    parts["total"] = sum(parts.values())
    return parts


def step_flops(cfg: Dict[str, Any], d: Dict[str, float]) -> Dict[str, float]:
    """The model's operations of the rows the steps counted in `d` ran."""
    rows = d["rows_decoded"] + d["prefill_chunk_tokens"]
    heads = cfg["num_attention_heads"] + cfg["num_key_value_heads"]
    parts = {
        "matmuls": 2.0 * rows * cfg["num_hidden_layers"]
        * layer_matmul_params(cfg),
        # a decode row's logits, and one row's a chunk
        "head": 2.0 * (d["rows_decoded"] + d["steps_with_chunk"])
        * cfg["hidden_size"] * cfg["vocab_size"],
        "retention": 2.0 * rows * cfg["num_hidden_layers"] * heads
        * phi_rows(cfg) * cfg["head_dim"],
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

"""Runner `serve_dp_mellum`: `serve_dp` for the Mellum 2 family. The served
path, the clocks, the traffic, the lateness rules, the second window and
every limit are `serve_dp`'s own: `run` here is `serve_dp.run` with the seams
that know the model replaced for the call and put back after it. All of them
are module globals that `serve_dp` looks up when it uses them:

    serve_dp.model_overrides             configuration file -> MellumConfig fields
    serve_dp.sum_stats                   + the experts', the chunks' and the
                                         window layers' counters
    _inside.engine_reference_check       each sample replayed with its routing,
                                         its router's inputs and every layer's
                                         attention recorded; float32 reference
                                         from position 0, experts in blocks
    serve_dp.judge_check                 + the replays, the routing margins,
                                         the router in float32 and each kind
                                         of layer's attention
    serve_dp.CHECK_TOLERANCE_BF16_STEPS  the logit gap's limit, below

The check's prompts are the traffic file's (`check.prompt_tokens`: inside the
window, across it, and past the ring's wrap and YaRN's original length), run
through the timed path by `serve_dp.check_requests` as it stands.

A traced run's check also writes the compiled steps' scopes (lib/scopes.py)
into the run's output directory, for `moe_device_share`.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List

from benchmark.lib.config import CellFailure
from benchmark.runners import _inside, _inside_mellum, serve_dp

# The check's limits. Each lies between two readings taken on v5e (PR 53,
# PERF.md section 6): the largest the program gave over its seeds, and what
# the reference gives when computed as the configuration does not state it
# (lib/reference_mellum.PLANTS and the bf16 router), judged as the program is.
#
# How far under a position's largest reference logit a returned token's logit
# may lie, in bf16 steps of the largest |logit| (serve_dp.py explains the
# measure; 6 there, 8 for Ling and Solar). The reference runs from position 0
# with the program's experts, so what is left is precision: bf16 activations
# through 8 layers that each end in a sum over 8 experts, over prompts of up
# to 9,300 tokens. The program read 0.6 to 3.4 steps over thirty-two seeds; the
# window layers run full 67 and 83, the full layers windowed 16 and 39, the
# plain rotary on them 11 and 21 (two seeds).
CHECK_TOLERANCE_BF16_STEPS = 8.0
# How far under the reference's own cut the program's routing may lie, in the
# logit steps of lib/reference_mellum.routing_margins: the worst of the
# ~750,000 token-expert pairs a run. The program read 14.9 to 24.1 over
# thirty-two seeds; the nearest plant, a window one key off, read 44, 62, 65
# and 89 on two seeds (float8 keys and values 98 and 125, YaRN's factor left
# out 150 and 161, the kinds swapped 320 to 644). 32 is near the middle of
# 24.1 and 44 by ratio: 1.33 times of room above, 1.37 below.
ROUTER_TOLERANCE_STEPS = {"expert_steps": 32.0}
# What the configuration states and the logits alone may not show, each held
# on what the program's own decode steps computed:
# - `router_f32_steps`: the router's softmax against the float32 router's on
#   the program's own normed inputs, in float32 steps of the logits
#   (reference_mellum.router_float32_steps). The program read 56.6 to 60.9
#   on the chip (its float32 matmul is the MXU's six bf16 passes, 1.2 on the
#   CPU); second reading: weights and logits in bf16, 5,607 to 8,194. 600
#   leaves both ten times.
# - `attn_window_error`, `attn_full_error`: each kind of layer's attention
#   before W_o at the decode steps against the reference's at the same
#   positions, the worst layer's and step's |got - want| / |want|. The
#   program's side carries bf16 activations through the layers before it:
#   0.015 to 0.025 and 0.024 to 0.034 over thirty-two seeds. A window layer run
#   full reads 0.77, a full layer run windowed 0.40 to 0.49, with the plain
#   rotary 0.29 to 0.40, without YaRN's factor 0.20 to 0.26 (and the window
#   layers behind it 0.06 to 0.07). Keys and values in float8 read 0.078 and
#   0.095 (0.062 and 0.066): on one seed of two under the limit, so that
#   plant is held by the routing margin alone. A window one key off reads
#   0.020 to 0.048 against 0.010 on the same sample, under the limit: on the
#   chip one key of 1,024 is inside what bf16 leaves, and only the routing
#   margins notice it; the CPU tests hold the window's edge in float32.
MECHANISM_LIMITS = {"router_f32_steps": 600.0, "attn_window_error": 0.08,
                    "attn_full_error": 0.08}
_SUM_STATS, _JUDGE_CHECK = serve_dp.sum_stats, serve_dp.judge_check
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "window_positions", "chunk_keys_read",
            "chunk_pairs", "steps_with_chunk", "prefill_chunk_tokens",
            "prefill_chunk_pad_tokens", "kv_positions_live",
            "attn_positions_live", "window_bytes", "kv_bytes")


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as MellumConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog = cfg, cfg["program"]
    full = m["rope_parameters"]["full_attention"]
    plain = m["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], plain["rope_type"]) != ("yarn", "default") or (
            full["rope_theta"] != plain["rope_theta"]):
        raise CellFailure("models/mellum.py gives the full layers YaRN and "
                          "the window layers the plain rotary, one theta")
    kinds = m["layer_types"]
    period = kinds.index("full_attention") + 1
    ids = tuple(prog["layer_ids"])
    if len(ids) != m["num_hidden_layers"] or any(
            (k == "full_attention") != (i % period == period - 1)
            for i, k in enumerate(kinds)):
        raise CellFailure("models/mellum.py places a full layer at the end "
                          "of every period of layer_types")
    if set(m["mlp_layer_types"]) != {"sparse"}:
        raise CellFailure("models/mellum.py has experts in every layer")
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"], "layer_ids": ids,
        "full_period": period, "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "sliding_window": m["sliding_window"],
        "rope_theta": float(plain["rope_theta"]),
        "yarn_factor": float(full["factor"]),
        "yarn_original_len": full["original_max_position_embeddings"],
        "yarn_beta_fast": float(full["beta_fast"]),
        "yarn_beta_slow": float(full["beta_slow"]),
        "yarn_attention_factor": float(full["attention_factor"]),
        "norm_eps": m["rms_norm_eps"],
        "moe_ffn_dim": m["moe_intermediate_size"],
        "n_experts": m["num_experts"], "top_k": m["num_experts_per_tok"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
    }


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file as lib/reference_mellum.py and the readers
    take it: its published keys with the program section's layer ids."""
    return {**cfg, "layer_ids": list(cfg["program"]["layer_ids"])}


def sum_stats(per_rank: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = _SUM_STATS(per_rank)
    out.update({k: sum(s[k] for s in per_rank) for k in COUNTERS})
    return out


def judge_check(gaps: List[Dict[str, Any]], tol_steps: float) -> Dict[str, Any]:
    """`serve_dp.judge_check` on the logit gaps, and: every replay returned
    the served path's tokens, the program's routing lies within the margins
    of the reference's own, its router is the float32 one on its inputs and
    each kind of layer's attention is the reference's. `plants`: the longest
    sample judged again under each planted departure (the traffic file's
    `check.plants`), by the same limits, with the first it reads over."""
    check = _JUDGE_CHECK(gaps, tol_steps)
    check["replays_equal"] = all(g["replay_equal"] for g in gaps)
    check["prompt_tokens"] = [g["prompt_tokens"] for g in gaps]
    for key, limit in ROUTER_TOLERANCE_STEPS.items():
        check[key] = max(g["routing"][key] for g in gaps)
        check[f"{key}_limit"] = limit
    check["same_experts_min"] = min(g["routing"]["same_experts"] for g in gaps)
    for key, limit in MECHANISM_LIMITS.items():
        check[key] = max(g[key] for g in gaps)
        check[f"{key}_limit"] = limit
    # the second reading, which a sound check reads over the limit
    check["router_f32_steps_bf16"] = max(
        g["router_f32_steps_bf16"] for g in gaps)
    check["seconds"] = {k: round(sum(g.get("seconds", {}).get(k, 0.0)
                                     for g in gaps), 1)
                        for k in ("replay", "reference")}
    limits = {**ROUTER_TOLERANCE_STEPS, **MECHANISM_LIMITS}
    check["ok"] = bool(check["ok"] and check["replays_equal"]
                       and all(check[k] <= v for k, v in limits.items()))
    planted = {}
    for g in gaps:
        for name, p in g.get("plants", {}).items():
            low = _JUDGE_CHECK([{**p, "argmax_equal": 0}], tol_steps)
            reads = {"gap_steps": low["worst_gap_bf16_steps"],
                     **{k: p[k] for k in limits if k in p}}
            over = [k for k, v in reads.items()
                    if v > (tol_steps if k == "gap_steps" else limits[k])]
            planted[name] = {**reads, "fails_by": over}
    if planted:
        check["plants"] = planted
    return check


def run(ctx) -> Dict[str, Any]:
    # a program without the family (any parent of the PR that added it)
    # fails here, at once, and not in an engine actor's constructor
    family = ctx.config["program"]["preset"].partition(":")[0]
    try:
        from ray_tpu.llm import MODEL_FAMILIES
    except ImportError:
        MODEL_FAMILIES = {}
    if family not in MODEL_FAMILIES:
        raise CellFailure(
            f"this program has no model family {family!r} "
            f"(ray_tpu.llm.MODEL_FAMILIES: {sorted(MODEL_FAMILIES) or 'none'})")
    scopes_path = (os.path.join(ctx.out_dir, "scopes.json")
                   if ctx.trace else None)
    seams = {
        (serve_dp, "model_overrides"): model_overrides,
        (serve_dp, "sum_stats"): sum_stats,
        (serve_dp, "judge_check"): judge_check,
        (serve_dp, "CHECK_TOLERANCE_BF16_STEPS"): CHECK_TOLERANCE_BF16_STEPS,
        (_inside, "engine_reference_check"): functools.partial(
            _inside_mellum.engine_reference_check,
            config=reference_hp(ctx.config), scopes_path=scopes_path,
            plants=tuple(ctx.traffic["check"].get("plants", ()))),
    }
    saved = {key: getattr(*key) for key in seams}
    try:
        for (module, name), new in seams.items():
            setattr(module, name, new)
        art = serve_dp.run(ctx)
    finally:
        for (module, name), old in saved.items():
            setattr(module, name, old)
    art["config"] = reference_hp(ctx.config)
    art["scopes_path"] = scopes_path
    return art

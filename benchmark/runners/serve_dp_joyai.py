"""Runner `serve_dp_joyai`: `serve_dp` for the JoyAI-LLM-Flash family. The
served path, the clocks, the traffic, the lateness rules, the second window
and every limit are `serve_dp`'s own: `run` here is `serve_dp.run` with the
seams that know the model replaced for the call and put back after it. All of
them are module globals that `serve_dp` looks up when it uses them:

    serve_dp.model_overrides             configuration file -> JoyAIConfig fields
    serve_dp.sum_stats                   + the experts', the latents' and the
                                         chunks' counters
    serve_dp.warm_requests               the first request of a session a tenant:
                                         they leave the system prompts' blocks,
                                         of which the check is served from one
    _inside.engine_reference_check       the check session's turns replayed
                                         with their routing recorded; float32
                                         reference from position 0 on the last
                                         turn's conversation; the pool's rows
    serve_dp.judge_check                 + the replays, the routing margins, the
                                         router in float32, the cache and the
                                         blocks the prefix cache served
    serve_dp.CHECK_TOLERANCE_BF16_STEPS  the logit gap's limit, below

The check's requests are one session of its own (the traffic file's
`check.session_of_client`) through the timed path, by
`serve_dp.check_requests` as it stands.

A traced run's check also writes the compiled steps' scopes (lib/scopes.py)
into the run's output directory, for `mla_device_share` and
`moe_device_share`.
"""

from __future__ import annotations

import functools
import importlib
import os
from typing import Any, Dict, List

from benchmark.lib.config import CellFailure
from benchmark.runners import _inside, _inside_joyai, serve_dp

# The check's limits. Each lies between two readings taken on v5e (PR 60,
# PERF.md section 6): the largest the program gave over its seeds, and what
# the reference, or the program's own cache, gives in the precision below the
# one the configuration states (`second_readings` in the traffic file's
# check), judged as the program is.
#
# How far under a position's largest reference logit a served token's logit
# may lie, in bf16 steps of the largest |logit| (serve_dp.py explains the
# measure; 6 there, 8 for Ling and Solar and for their reason). The reference
# runs from position 0 with the program's experts, so what is left is
# precision: bf16 activations through 40 layers of which 39 end in a sum over
# the held experts and a shared one. Second reading: the reference with its
# activations in float8_e4m3fn (`gaps_float8`).
CHECK_TOLERANCE_BF16_STEPS = 8.0
# How far under the reference's own cut the program's routing may lie, in the
# steps of lib/reference_joyai.routing_margins (one: what one bf16 step on
# every component of the router's input moves a sigmoid score): the worst of
# the conversation's ~4.5 million token-expert pairs. Ling's and Solar's
# limit, for their reason.
ROUTER_TOLERANCE_STEPS = {"expert_steps": 40.0}
# What the configuration states and the logits alone may not show:
# - `router_f32_steps`: the router's scores against the float32 router's on
#   the program's own normed inputs at the replays' decode steps, in float32
#   steps (reference_ling.router_float32_steps). Second reading: weights and
#   logits in bf16.
# - `cache_error`: the pool's rows of the conversation's blocks against the
#   reference's [c | roped k_r], |got - want| / |want| over a whole layer,
#   the worst layer's. The program's side carries bf16 activations through
#   the layers before it, so it grows with depth: 0.0029 in layer 0 to 0.019,
#   0.021 and 0.023 in layer 38 or 39 on three seeds (my chip runs, PR 60).
#   Second reading: the same rows rounded to float8_e4m3fn, which a cache
#   kept in that precision would read at least: 0.033, 0.034 and 0.035. The
#   limit is near the middle of 0.023 and 0.033 by ratio, 1.2 times of room
#   on either side: thin, for a mean over seven million values.
# - `cache_error_first`: layer 0's alone, where no layer's activations lie
#   before the cache's own precision: 0.0029 on each of three seeds against
#   0.026 in float8, three times of room on either side.
# - `cache_row_error`: the worst single position's: 0.10, 0.17 and 0.20 on
#   three seeds; a block that holds another's rows reads 1.4 on its own
#   sixteen positions and moves a layer's error by a thirtieth of that.
MECHANISM_LIMITS = {"router_f32_steps": 32.0, "cache_error": 0.0275,
                    "cache_error_first": 0.009, "cache_row_error": 0.5}
_SUM_STATS, _JUDGE_CHECK = serve_dp.sum_stats, serve_dp.judge_check
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "latent_positions_read", "chunk_latents_read",
            "latent_positions_live", "latent_bytes", "attn_positions_live",
            "attn_positions_shared", "steps_with_chunk",
            "prefill_chunk_tokens", "prefill_chunk_pad_tokens")


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as JoyAIConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog = cfg, cfg["program"]
    if (m["n_group"], m["topk_group"], m["scoring_func"], m["topk_method"],
            m["norm_topk_prob"], m["n_shared_experts"], m["moe_layer_freq"],
            m["rope_scaling"]) != (1, 1, "sigmoid", "noaux_tc", True, 1, 1,
                                   None):
        raise CellFailure(
            "models/joyai.py routes by sigmoid scores over one group with a "
            "score-correction bias and normalised weights, has one shared "
            "expert, experts in every layer past the dense ones and the "
            "plain rotary")
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"],
        "first_k_dense": m["first_k_dense_replace"],
        "n_heads": m["num_attention_heads"],
        "q_lora_rank": m["q_lora_rank"], "kv_lora_rank": m["kv_lora_rank"],
        "qk_nope_dim": m["qk_nope_head_dim"],
        "qk_rope_dim": m["qk_rope_head_dim"], "v_head_dim": m["v_head_dim"],
        "rope_theta": float(m["rope_theta"]),
        "rope_interleave": m["rope_interleave"],
        "norm_eps": m["rms_norm_eps"], "ffn_dim": m["intermediate_size"],
        "moe_ffn_dim": m["moe_intermediate_size"],
        "n_experts": prog["router_num_experts"],
        "top_k": m["num_experts_per_tok"],
        "routed_scale": m["routed_scaling_factor"],
        "held_start": prog["held_experts_start"],
        "n_held": m["n_routed_experts"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
    }


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file as lib/reference_joyai.py and the readers take
    it: its published keys with the program section's router width and held
    range (`num_experts`: the held experts, under the name
    `moe_load_max_over_mean` reads)."""
    prog = cfg["program"]
    return {**cfg, "router_num_experts": prog["router_num_experts"],
            "held_experts_start": prog["held_experts_start"],
            "num_experts": cfg["n_routed_experts"]}


def sum_stats(per_rank: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = _SUM_STATS(per_rank)
    out.update({k: sum(s[k] for s in per_rank) for k in COUNTERS})
    return out


def warm_requests(traffic: Dict[str, Any], seed: int) -> List[Dict[str, Any]]:
    """The first request of a session of its own a tenant (the traffic file's
    `warm.sessions_of_clients`, the check's tenant first), two tokens each:
    each runs a whole system prompt as chunks and leaves its blocks in the
    prefix cache, as a deployment's long-lived system prompts lie there: the
    check's first turn is served from the first one's, and the ramp's
    sessions all find theirs, so the window does not open on the tenants'
    cold prompts. The engine warms every program it can dispatch at its
    start."""
    generator = importlib.import_module(
        f"benchmark.traffic.{traffic['generator']}")
    return [{**next(generator.stream(traffic, seed, int(client))),
             "max_tokens": 2, "tag": "warm"}
            for client in traffic["warm"]["sessions_of_clients"]]


def judge_check(gaps: List[Dict[str, Any]], tol_steps: float) -> Dict[str, Any]:
    """`serve_dp.judge_check` on the logit gaps, and: the program's routing
    lies within the margins of the reference's own, its router is the
    float32 one on its inputs, the pool holds the reference's latents, and
    the prefix cache served the session the blocks it must have
    (`block_hits`). Where each replay's tokens part from the served turn's
    is reported (`replays_part_at`) and not held: on the chip a replay runs
    a prompt's last rows in a chunk of another width than the served turn
    did, another program, and bf16 through 40 layers then parts the greedy
    tokens of seeded weights within a few positions (PERF.md section 6,
    PR 60); what the served tokens are held to is the reference's logits."""
    check = _JUDGE_CHECK(gaps, tol_steps)
    g = gaps[0]
    parts = g["replays_part_at"]
    check["replays_part_at"] = parts
    check["replays_equal"] = all(p < 0 for p in parts)
    check["prompt_tokens"] = g["prompt_tokens"]
    check["served_tokens_judged"] = g["served_tokens_judged"]
    for key, limit in ROUTER_TOLERANCE_STEPS.items():
        check[key] = g["routing"][key]
        check[f"{key}_limit"] = limit
    check["same_experts_min"] = g["routing"]["same_experts"]
    for key, limit in MECHANISM_LIMITS.items():
        check[key] = g[key]
        check[f"{key}_limit"] = limit
    check["cache_worst_layer"] = g["cache_worst_layer"]
    # for the log: every layer's reading, the routing's with its position
    check["by_layer"] = {k: g[k] for k in ("expert_steps_by_layer",
                                           "cache_error_by_layer") if k in g}
    check["cached_positions"] = g["cached_positions"]
    check["block_hits"] = g["block_hits"]
    check["block_hits_least"] = g["block_hits_least"]
    if "gaps_float8" in g:
        low = _JUDGE_CHECK([{**g, "gaps": g["gaps_float8"]}], tol_steps)
        check["second_readings"] = {
            "gap_steps_float8": low["worst_gap_bf16_steps"],
            "expert_steps_float8": g["expert_steps_float8"],
            "router_f32_steps_bf16": g["router_f32_steps_bf16"],
            "cache_error_float8": g["cache_error_float8"],
            "cache_error_first_float8": g["cache_error_first_float8"]}
    check["seconds"] = {k: round(v, 1) for k, v in g["seconds"].items()}
    limits = {**ROUTER_TOLERANCE_STEPS, **MECHANISM_LIMITS}
    check["ok"] = bool(check["ok"] and g["block_hits"] >= g["block_hits_least"]
                       and g["cached_positions"] >= g["cached_positions_least"]
                       and all(check[k] <= v for k, v in limits.items()))
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
        (serve_dp, "warm_requests"): warm_requests,
        (serve_dp, "judge_check"): judge_check,
        (serve_dp, "CHECK_TOLERANCE_BF16_STEPS"): CHECK_TOLERANCE_BF16_STEPS,
        (_inside, "engine_reference_check"): functools.partial(
            _inside_joyai.engine_reference_check,
            config=reference_hp(ctx.config), scopes_path=scopes_path,
            system_tokens=int(ctx.traffic["system_tokens"]),
            second_readings=bool(ctx.traffic["check"].get("second_readings"))),
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

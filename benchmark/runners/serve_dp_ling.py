"""Runner `serve_dp_ling`: `serve_dp` for the Ling family. The served path,
the clocks, the traffic, the lateness rules, the second window and every
limit are `serve_dp`'s own: `run` here is `serve_dp.run` with the seams
that know the model replaced for the call and put back after it. All of them
are module globals that `serve_dp` looks up when it uses them:

    serve_dp.model_overrides             configuration file -> LingConfig fields
    serve_dp.sum_stats                   + the expert layers' counters
    _inside.engine_reference_check       routing recorded, float32 reference
                                         with the held experts in blocks
    serve_dp.judge_check                 + the routing margins, the replay, the
                                         router and the state in float32
    serve_dp.CHECK_TOLERANCE_BF16_STEPS  the logit gap's limit, below

A traced run's check also writes the compiled steps' scopes (lib/scopes.py)
into the run's output directory, for the three `*_device_share` readers.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List

from benchmark.lib.config import CellFailure
from benchmark.runners import _inside, _inside_ling, serve_dp

# The check's limits. Each lies between two readings taken on v5e (PR 29,
# PERF.md section 6): the largest the program gave over its seeds, and what
# the reference itself gives when computed in the precision below the one the
# configuration states, judged as the program is.
#
# How far under a position's largest reference logit a returned token's logit
# may lie, in bf16 steps of the largest |logit| (serve_dp.py explains the
# measure; 6 there). The reference is given the program's experts, so what is
# left is precision: bf16 activations through 7 layers of which 6 end in a sum
# over 8 experts and a shared one. Second reading: the reference with its
# activations (the residual stream and every normed input) in float8_e4m3fn.
CHECK_TOLERANCE_BF16_STEPS = 8.0
# How far under the reference's own cut the program's routing may lie, in the
# steps of lib/reference_ling.routing_margins (one step: what one bf16 step
# on every component of the router's input moves a sigmoid score). The worst
# of ~200,000 token-expert pairs a run, so it grows slowly with their number.
# It holds the selection (bias, groups, top-k) through the activations' noise;
# second reading as above. The router's and the state's own precision are
# below that noise and are held by the next two.
ROUTER_TOLERANCE_STEPS = {"expert_steps": 40.0, "group_steps": 20.0}
# The mechanisms the configuration states in float32, each held on the inputs
# the program's own decode steps computed it from
# (reference_ling.mechanism_readings), so the activations play no part:
# - the router's scores against the float32 router's, in float32 steps (one:
#   every product of the dot rounded to float32 to one side). Second reading:
#   the reference's router with weights and logits in bf16;
# - a slot's recurrent state after the replay's decode steps (`state_steps`
#   of the traffic file past a 32-token answer) against the reference's
#   token-by-token scan from the state the prefill left, the worst head's
#   relative error. Second reading: the scan with its state rounded to bf16
#   after every token.
MECHANISM_LIMITS = {"router_f32_steps": 32.0, "state_error": 1e-4}
# serve_dp's own, which the replacements below build on
_SUM_STATS, _JUDGE_CHECK = serve_dp.sum_stats, serve_dp.judge_check
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "latent_positions_live",
            # turns of the engine's loop that took over a second: the summary
            # line's stats_open / stats_close tell a stall from a slower step
            "loop_stalls", "loop_stall_s", "loop_stall_admit_s")


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as LingConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog = cfg, cfg["program"]
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"],
        "layer_ids": tuple(prog["layer_ids"]),
        "layer_group_size": m["layer_group_size"],
        "first_k_dense": m["first_k_dense_replace"],
        "n_heads": m["num_attention_heads"], "head_dim": m["head_dim"],
        "conv_kernel": m["short_conv_kernel_size"],
        "kda_gate_low": float(m["kda_lower_bound"]),
        "kv_lora_rank": m["kv_lora_rank"],
        "qk_nope_dim": m["qk_nope_head_dim"],
        "qk_rope_dim": m["qk_rope_head_dim"], "v_head_dim": m["v_head_dim"],
        "rope_theta": float(m["rope_theta"]), "norm_eps": m["rms_norm_eps"],
        "ffn_dim": m["intermediate_size"],
        "moe_ffn_dim": m["moe_intermediate_size"],
        "n_experts": prog["router_num_experts"], "n_group": m["n_group"],
        "topk_group": m["topk_group"], "top_k": m["num_experts_per_tok"],
        "routed_scale": m["routed_scaling_factor"],
        "held_start": prog["held_experts_start"], "n_held": m["num_experts"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
    }


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file as lib/reference_ling.py reads it: its
    published keys with the program section's layer ids and held range."""
    prog = cfg["program"]
    return {**cfg, "layer_ids": list(prog["layer_ids"]),
            "router_num_experts": prog["router_num_experts"],
            "held_experts_start": prog["held_experts_start"]}


def sum_stats(per_rank: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = _SUM_STATS(per_rank)
    out.update({k: sum(s[k] for s in per_rank) for k in COUNTERS})
    out["loop_stall_last_at"] = max(s["loop_stall_last_at"] for s in per_rank)
    return out


def judge_check(gaps: List[Dict[str, Any]], tol_steps: float) -> Dict[str, Any]:
    """`serve_dp.judge_check` on the logit gaps, and: every replay returned
    the served path's tokens (so what is judged is the answer's), the
    program's routing lies within the margins of the reference's own, and
    its router and recurrent state are the float32 ones on their inputs."""
    check = _JUDGE_CHECK(gaps, tol_steps)
    check["replays_equal"] = all(g["replay_equal"] for g in gaps)
    for key, limit in ROUTER_TOLERANCE_STEPS.items():
        check[key] = max(g["routing"][key] for g in gaps)
        check[f"{key}_limit"] = limit
    check["same_experts_min"] = min(g["routing"]["same_experts"] for g in gaps)
    for key, limit in MECHANISM_LIMITS.items():
        check[key] = max(g["mechanisms"][key] for g in gaps)
        check[f"{key}_limit"] = limit
        # the second reading, which a sound check reads over the limit
        check[f"{key}_bf16"] = max(g["mechanisms"][f"{key}_bf16"] for g in gaps)
    check["state_steps"] = max(g["mechanisms"]["state_steps"] for g in gaps)
    limits = {**ROUTER_TOLERANCE_STEPS, **MECHANISM_LIMITS}
    check["ok"] = bool(check["ok"] and check["replays_equal"]
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
        (serve_dp, "judge_check"): judge_check,
        (serve_dp, "CHECK_TOLERANCE_BF16_STEPS"): CHECK_TOLERANCE_BF16_STEPS,
        (_inside, "engine_reference_check"): functools.partial(
            _inside_ling.engine_reference_check,
            config=reference_hp(ctx.config), scopes_path=scopes_path,
            prefill_tokens=list(ctx.traffic["warm"]["prefill_tokens"]),
            state_steps=int(ctx.traffic["check"]["state_steps"])),
    }
    saved = {key: getattr(*key) for key in seams}
    try:
        for (module, name), new in seams.items():
            setattr(module, name, new)
        art = serve_dp.run(ctx)
    finally:
        for (module, name), old in saved.items():
            setattr(module, name, old)
    art["config"], art["scopes_path"] = ctx.config, scopes_path
    return art

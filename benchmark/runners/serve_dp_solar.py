"""Runner `serve_dp_solar`: `serve_dp` for the Solar-Open2 family. The
served path, the clocks, the traffic, the lateness rules, the second window
and every limit are `serve_dp`'s own: `run` here is `serve_dp.run` with the
seams that know the model replaced for the call and put back after it. All
of them are module globals that `serve_dp` looks up when it uses them:

    serve_dp.model_overrides             configuration file -> SolarConfig fields
    serve_dp.sum_stats                   + the experts', chunks' and snapshots'
                                         counters
    serve_dp.check_requests              the check session's document at the
                                         length the traffic file's check states
    _inside.engine_reference_check       routing recorded (the first replay
                                         cold, the others resumed from a
                                         snapshot), float32 reference from
                                         position 0 with the held experts in
                                         blocks
    serve_dp.judge_check                 + the routing margins, the replays,
                                         the resumes, the router and the state
                                         in float32
    serve_dp.CHECK_TOLERANCE_BF16_STEPS  the logit gap's limit, below

A traced run's check also writes the compiled steps' scopes (lib/scopes.py)
into the run's output directory, for the `*_device_share` readers.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List

from benchmark.lib.config import CellFailure
from benchmark.runners import _inside, _inside_solar, serve_dp

# The check's limits. Each lies between two readings taken on v5e (PR 46,
# PERF.md section 6): the largest the program gave over its seeds, and what
# the reference itself gives when computed in the precision below the one the
# configuration states, judged as the program is.
#
# How far under a position's largest reference logit a returned token's logit
# may lie, in bf16 steps of the largest |logit| (serve_dp.py explains the
# measure; 6 there, 8 for Ling). The reference runs from position 0 with the
# program's experts, so what is left is precision: bf16 activations through 4
# layers that each end in a sum over 8 experts and a shared one, over prompts
# of 8,192 tokens and more. Second reading: the reference with its activations
# (the residual stream and every normed input) in float8_e4m3fn.
CHECK_TOLERANCE_BF16_STEPS = 8.0
# How far under the reference's own cut the program's routing may lie, in the
# steps of lib/reference_ling.routing_margins. The worst of ~1,000,000
# token-expert pairs a run. Second reading as above.
ROUTER_TOLERANCE_STEPS = {"expert_steps": 40.0}
# The mechanisms the configuration states in float32, each held on the inputs
# the program's own steps computed it from
# (reference_solar.mechanism_readings), so the activations play no part:
# - the router's scores against the float32 router's, in float32 steps.
#   Second reading: the reference's router with weights and logits in bf16;
# - a slot's recurrent state after a resume from a snapshot, the chunks of
#   the question behind it and the replay's decode steps (`state_steps` of the
#   traffic file past the answer) against the reference's token-by-token
#   scan from the snapshot's state, the worst head's relative error. Second
#   reading: the scan with its state rounded to bf16 after every token.
# The state's limit is not Ling's 1e-4: here the state passes through
# `kda_chunked` (the resumed chunk and the question's), whose decays are
# exponentials of differences of cumulative logs, and reads 4.9e-5 to 5.3e-5
# on the chip against 1.7e-2 for a bf16 state: 1e-3 leaves both ~18 times.
MECHANISM_LIMITS = {"router_f32_steps": 32.0, "state_error": 1e-3}
# serve_dp's own, which the replacements below build on
_SUM_STATS, _JUDGE_CHECK, _CHECK_REQUESTS = (
    serve_dp.sum_stats, serve_dp.judge_check, serve_dp.check_requests)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "steps_with_chunk", "prefill_chunk_tokens",
            "prefill_chunk_pad_tokens", "chunk_positions_live",
            "chunk_attn_pairs",
            "snapshots_taken", "snapshots_restored", "snapshots_evicted",
            "snapshot_rerun_tokens", "snapshot_bytes", "state_bytes",
            "kv_positions_live", "attn_positions_live")


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as SolarConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog, lin = cfg, cfg["program"], cfg["linear_attn_config"]
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise CellFailure("models/solar.py gives a KDA layer as many key and "
                          "value heads as query heads")
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"],
        "layer_ids": tuple(prog["layer_ids"]),
        "gqa_period": m["gqa_interval"] + 1,
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "kda_heads": lin["num_heads"], "kda_head_dim": lin["head_dim"],
        "conv_kernel": lin["short_conv_kernel_size"],
        "gate_rank": prog["kda_gate_rank"],
        "beta_scale": 2.0 if m["kda_allow_neg_eigval"] else 1.0,
        "norm_eps": m["rms_norm_eps"],
        "moe_ffn_dim": m["moe_intermediate_size"],
        "n_experts": prog["router_num_experts"],
        "top_k": m["num_experts_per_tok"],
        "routed_scale": float(m["routed_scaling_factor"]),
        "held_start": prog["held_experts_start"],
        "n_held": m["n_routed_experts"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
    }


def reference_hp(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration file as lib/reference_solar.py reads it: its
    published keys with the program section's layer ids and held range."""
    prog = cfg["program"]
    return {**cfg, "layer_ids": list(prog["layer_ids"]),
            "router_num_experts": prog["router_num_experts"],
            "held_experts_start": prog["held_experts_start"]}


def reader_config(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """`art["config"]`: the configuration file, with the held experts' count
    also under the key `moe_load_max_over_mean` reads it by (Ling's)."""
    return {**cfg, "num_experts": cfg["n_routed_experts"]}


def sum_stats(per_rank: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = _SUM_STATS(per_rank)
    out.update({k: sum(s[k] for s in per_rank) for k in COUNTERS})
    return out


def check_requests(generator, traffic: Dict[str, Any], seed: int
                   ) -> List[Dict[str, Any]]:
    """`serve_dp.check_requests` with the session's document at the length
    the traffic file's check states (the window's documents keep theirs)."""
    doc = traffic["check"].get("document_tokens")
    if doc is not None:
        traffic = {**traffic, "document_tokens": {
            "dist": "uniform", "min": int(doc), "max": int(doc)}}
    return _CHECK_REQUESTS(generator, traffic, seed)


def judge_check(gaps: List[Dict[str, Any]], tol_steps: float) -> Dict[str, Any]:
    """`serve_dp.judge_check` on the logit gaps, and: every replay returned
    the served path's tokens (the first from position 0, so an answer given
    from a snapshot is the answer of a run from position 0), a served
    request and every replay but the first resumed from a snapshot, the
    program's routing lies within the margins of the reference's own, and its
    router and recurrent state are the float32 ones on their inputs."""
    check = _JUDGE_CHECK(gaps, tol_steps)
    check["replays_equal"] = all(g["replay_equal"] for g in gaps)
    check["resumed_from"] = [g["resume_from"] for g in gaps]
    check["served_resumed"] = min(g["served_resumed"] for g in gaps)
    check["resumed"] = bool(check["served_resumed"] >= 1 and len(gaps) > 1
                            and all(g["resume_from"] > 0 for g in gaps[1:]))
    for key, limit in ROUTER_TOLERANCE_STEPS.items():
        check[key] = max(g["routing"][key] for g in gaps)
        check[f"{key}_limit"] = limit
    check["same_experts_min"] = min(g["routing"]["same_experts"] for g in gaps)
    held = [g["mechanisms"] for g in gaps if "mechanisms" in g]
    for key, limit in MECHANISM_LIMITS.items():
        check[key] = max((m[key] for m in held), default=float("inf"))
        check[f"{key}_limit"] = limit
        # the second reading, which a sound check reads over the limit
        check[f"{key}_bf16"] = max((m[f"{key}_bf16"] for m in held), default=0.0)
    check["state_steps"] = max((m["state_steps"] for m in held), default=0)
    check["seconds"] = {k: round(sum(g.get("seconds", {}).get(k, 0.0)
                                     for g in gaps), 1)
                        for k in ("replay", "reference")}
    check["state_chunk_rows"] = max((m["chunk_rows"] for m in held), default=0)
    for key in ("fp8_activations", "bf16_state"):
        low = [_JUDGE_CHECK([{**g, **g[key], "argmax_equal": 0}], tol_steps)
               for g in gaps if key in g]
        if low:
            check[f"{key}_gap_steps"] = max(
                c["worst_gap_bf16_steps"] for c in low)
            check[f"{key}_expert_steps"] = max(
                g[key]["expert_steps"] for g in gaps if key in g)
    limits = {**ROUTER_TOLERANCE_STEPS, **MECHANISM_LIMITS}
    check["ok"] = bool(check["ok"] and check["replays_equal"]
                       and check["resumed"]
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
        (serve_dp, "check_requests"): check_requests,
        (serve_dp, "judge_check"): judge_check,
        (serve_dp, "CHECK_TOLERANCE_BF16_STEPS"): CHECK_TOLERANCE_BF16_STEPS,
        (_inside, "engine_reference_check"): functools.partial(
            _inside_solar.engine_reference_check,
            config=reference_hp(ctx.config), scopes_path=scopes_path,
            state_steps=int(ctx.traffic["check"]["state_steps"]),
            second_readings=bool(
                ctx.traffic["check"].get("second_readings", False))),
    }
    saved = {key: getattr(*key) for key in seams}
    try:
        for (module, name), new in seams.items():
            setattr(module, name, new)
        art = serve_dp.run(ctx)
    finally:
        for (module, name), old in saved.items():
            setattr(module, name, old)
    art["config"], art["scopes_path"] = reader_config(ctx.config), scopes_path
    return art

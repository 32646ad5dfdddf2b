"""Runner `serve_dp_brumby`: `serve_dp` for the Brumby family. The served
path, the clocks, the traffic, the lateness rules, the second window and
every limit are `serve_dp`'s own: `run` here is `serve_dp.run` with the
seams that know the model replaced for the call and put back after it. All
of them are module globals that `serve_dp` looks up when it uses them:

    serve_dp.model_overrides             configuration file -> BrumbyConfig fields
    serve_dp.sum_stats                   + the chunks', the snapshots' and
                                         the state's counters
    serve_dp.check_requests              the check task's header at the length
                                         the traffic file's check states
    _inside.engine_reference_check       replays (the first cold, the others
                                         resumed from a snapshot), float32
                                         reference from position 0, the state
                                         and the gate on the program's inputs
    serve_dp.judge_check                 + the cold replay, the resumes, the
                                         state and the gate in float32
    serve_dp.CHECK_TOLERANCE_BF16_STEPS  the logit gap's limit, below

A traced run's check also writes the compiled steps' scopes (lib/scopes.py)
into the run's output directory.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List

from benchmark.lib.config import CellFailure
from benchmark.runners import _inside, _inside_brumby, serve_dp

# The check's limits. Each lies between two readings taken on v5e (PR 48,
# PERF.md section 6): the largest the program gave over its seeds, and what
# the same mechanism gives when computed in the precision below the one the
# configuration states.
#
# How far under a position's largest reference logit a returned token's logit
# may lie, in bf16 steps of the largest |logit| (serve_dp.py explains the
# measure, and 6 is its own). The reference runs from position 0, so what is
# left is precision: bf16 activations through 6 layers over prompts of 8,192
# tokens and more. The program read 1.13 to 1.74 steps on the chip; the
# second reading, the reference with its activations (the residual stream and
# every normed input) in float8_e4m3fn, 11.2.
CHECK_TOLERANCE_BF16_STEPS = 6.0
# The mechanisms the configuration states in float32, each held on the
# inputs the program's own steps computed it from
# (reference_brumby.mechanism_readings), so the activations play no part:
# - `state_error`: a slot's state after the header's chunks, a snapshot, a
#   restore, the item's chunks and the replay's decode steps (`state_steps`
#   of the traffic file past the answer) against the reference's direct sum
#   over every position from 0, the worst layer's and head's relative error:
#   3.9e-5 on the chip (5.6e-4 while the decode rows' gate was exp(log
#   sigmoid(z)): the chip's exp near 1). Second reading: the rows behind the
#   snapshot run as a recurrence whose state is rounded to bf16 after every
#   position, 0.12 to 0.37.
# - `gamma_error`: the log gate against the float32 gate on the program's own
#   normed input, the worst relative error: 0.0 on the chip. Second reading:
#   the gate's logit and log sigmoid in bf16, 0.035.
MECHANISM_LIMITS = {"state_error": 1e-3, "gamma_error": 1e-3}
_SUM_STATS, _JUDGE_CHECK, _CHECK_REQUESTS = (
    serve_dp.sum_stats, serve_dp.judge_check, serve_dp.check_requests)
COUNTERS = ("rows_decoded", "steps_with_chunk", "prefill_chunk_tokens",
            "prefill_chunk_pad_tokens", "snapshots_taken",
            "snapshots_restored", "snapshots_evicted", "snapshots_shared",
            "snapshot_rerun_tokens", "snapshot_bytes", "state_bytes",
            "kv_positions_live", "attn_positions_live")


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as BrumbyConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog = cfg, cfg["program"]
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"],
        "n_heads": m["num_attention_heads"],
        "n_kv_heads": m["num_key_value_heads"], "head_dim": m["head_dim"],
        "ffn_dim": m["intermediate_size"], "rope_theta": m["rope_theta"],
        "norm_eps": m["rms_norm_eps"], "ret_eps": m["retention_eps"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
    }


def sum_stats(per_rank: List[Dict[str, Any]]) -> Dict[str, Any]:
    out = _SUM_STATS(per_rank)
    out.update({k: sum(s[k] for s in per_rank) for k in COUNTERS})
    return out


def check_requests(generator, traffic: Dict[str, Any], seed: int
                   ) -> List[Dict[str, Any]]:
    """`serve_dp.check_requests` with the task's header at the length the
    traffic file's check states (the window's headers keep theirs)."""
    header = traffic["check"].get("header_tokens")
    if header is not None:
        traffic = {**traffic, "header_tokens": {
            "dist": "uniform", "min": int(header), "max": int(header)}}
    return _CHECK_REQUESTS(generator, traffic, seed)


def judge_check(gaps: List[Dict[str, Any]], tol_steps: float) -> Dict[str, Any]:
    """`serve_dp.judge_check` on the logit gaps, and: the cold replay
    returned the served cold run's tokens (the program gives one answer to
    one prompt run one way), a served request was answered from a snapshot
    another request left and the later replays resumed from one, and the
    program's state and gate are the float32 ones on their inputs. Where a
    resumed replay parted from its served answer is reported and not held: a
    replay finds more cached than the served request did and cuts its
    chunks elsewhere, which moves the last bits (each served token is held
    to the reference whichever way it was computed)."""
    check = _JUDGE_CHECK(gaps, tol_steps)
    check["replays_equal"] = bool(gaps[0]["replay_equal"])
    check["replays_part_at"] = [g.get("replay_parts_at", -1) for g in gaps]
    check["resumed_from"] = [g["resume_from"] for g in gaps]
    check["served_shared"] = min(g["served_shared"] for g in gaps)
    check["resumed"] = bool(check["served_shared"] >= 1 and len(gaps) > 2
                            and all(g["resume_from"] > 0 for g in gaps[2:]))
    held = [g["mechanisms"] for g in gaps if "mechanisms" in g]
    for key, limit in MECHANISM_LIMITS.items():
        check[key] = max((m[key] for m in held), default=float("inf"))
        check[f"{key}_limit"] = limit
        # the second reading, which a sound check reads over the limit
        check[f"{key}_bf16"] = max((m[f"{key}_bf16"] for m in held), default=0.0)
    check["state_steps"] = max((m["state_steps"] for m in held), default=0)
    check["state_rows"] = max((m["state_rows"] for m in held), default=0)
    check["seconds"] = {k: round(sum(g.get("seconds", {}).get(k, 0.0)
                                     for g in gaps), 1)
                        for k in ("replay", "reference")}
    low = [_JUDGE_CHECK([{**g, **g["fp8_activations"], "argmax_equal": 0}],
                        tol_steps) for g in gaps if "fp8_activations" in g]
    if low:
        check["fp8_activations_gap_steps"] = max(
            c["worst_gap_bf16_steps"] for c in low)
    check["ok"] = bool(check["ok"] and check["replays_equal"]
                       and check["resumed"]
                       and all(check[k] <= v
                               for k, v in MECHANISM_LIMITS.items()))
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
            _inside_brumby.engine_reference_check,
            config=ctx.config, scopes_path=scopes_path,
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
    art["config"], art["scopes_path"] = ctx.config, scopes_path
    return art

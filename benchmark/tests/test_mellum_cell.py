"""The Mellum 2 cell's own pieces on the CPU: the configuration file against
the catalog's numbers, the traffic generator, the runner's seams, the byte,
operation and counter readers, the reference at the tiny size against the
family, planted departures that the check must refuse, and the rehearsal twin
end to end.

    python -m pytest benchmark/tests/test_mellum_cell.py -q        (not part of tier-1)
"""

import json
import os

import numpy as np
import pytest

from benchmark.layer_metrics import (mellum_decode_attention_roofline,
                                     mellum_step_hbm_roofline, mellum_step_mfu,
                                     window_positions_share)
from benchmark.lib import bytes_mellum, reference_mellum
from benchmark.runners import serve_dp, serve_dp_mellum
from benchmark.tests.test_rehearsal import RESULT_KEYS, ROOT, load, run_cell
from benchmark.traffic import _common, closed_mixed

CONFIG = load("configs", "mellum2-12b-l8.json")
TINY = load("configs", "tiny-mellum.json")
TRAFFIC = load("traffic", "repochat-closed.json")
CELL = "mellum2-repochat-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["attn_window_device_share", "attn_full_device_share",
       "window_positions_share", "mellum_decode_attention_roofline",
       "mellum_step_hbm_roofline", "mellum_step_mfu"]


def test_the_configuration_keeps_every_published_number_but_the_depth():
    assert set(CONFIG["reduced"]) == {"num_hidden_layers"}
    cut = CONFIG["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"]) == (28, 8)
    assert CONFIG["num_hidden_layers"] == 8
    kept = {"hidden_size": 2304, "num_attention_heads": 32, "head_dim": 128,
            "num_key_value_heads": 4, "num_experts": 64,
            "num_experts_per_tok": 8, "moe_intermediate_size": 896,
            "intermediate_size": 7168, "sliding_window": 1024,
            "vocab_size": 98304, "max_position_embeddings": 131072,
            "rms_norm_eps": 1e-06}
    assert {k: CONFIG[k] for k in kept} == kept
    # the two lists stay whole, as published: `program.layer_ids` names the
    # kept layers
    assert CONFIG["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 7
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 28
    assert CONFIG["program"]["layer_ids"] == list(range(8))
    assert reference_mellum.layer_kinds(
        serve_dp_mellum.reference_hp(CONFIG)) == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == {"num_hidden_layers"}
        assert CONFIG["rope_parameters"] == row["config"]["rope_parameters"]
    assumed = " ".join(CONFIG["assumed"])
    with open(os.path.join(ROOT, "tests", "test_mellum.py")) as f:
        tier1 = f.read()
    for test in ("test_assumed_q_and_k_are_normed_per_head",
                 "test_assumed_router_is_a_float32_softmax_before_the_top_k",
                 "test_assumed_yarn_range_is_truncated_to_whole_dimensions",
                 "test_yarn_at_the_published_numbers",
                 "test_assumed_rotary_in_halves",
                 "test_assumed_no_mtp_head_and_no_shared_expert",
                 "test_a_planted_departure_fails_the_check"):
        assert test in assumed and f"def {test}(" in tier1
    for word in ("deployment", "bytes", "engine_note"):
        assert CONFIG[word]


def test_the_cell_and_its_traffic_are_the_issues_to_the_number():
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2-12b-l8", "repochat-closed", 1)
    assert cell["end_to_end"] == ["out_tokens_per_s", "setup_s"]
    assert len(cell["why"]) <= 200
    t = TRAFFIC
    # trace_s 1, not the issue's 2: two of three traced windows at 2 s broke
    # the runner's counter_share on the chip (the file's trace_note). The
    # issue's one allowed change is taken: 6 repository callers for 8, a
    # pool of 18 for 24, 46 slots and callers for 48 (six seeds spread 5.0%
    # with 8 and 2.1% with 6: PERF.md section 7)
    assert (t["generator"], t["clients"], t["ramp_s"], t["trace_s"]) == (
        "closed_mixed", 46, 30, 1)
    repo, chat = t["classes"]
    assert (repo["name"], repo["clients"], repo["pool"]) == ("repository", 6, 18)
    assert repo["prompt_tokens"] == {"dist": "uniform", "min": 8192, "max": 32768}
    assert repo["answer_tokens"] == {"dist": "uniform", "min": 256, "max": 512}
    assert (chat["name"], chat["clients"], chat["pool"]) == ("chat", 40, 400)
    assert chat["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 0.8, "min": 64, "max": 2048}
    assert chat["answer_tokens"] == {"dist": "lognormal", "median": 128,
                                     "sigma": 0.6, "min": 32, "max": 384}
    assert t["check"]["prompt_tokens"] == [100, 1000, 1100, 9300]
    assert t["check"]["answer_tokens"] == 32 and t["check"]["plants"] == []
    e = CONFIG["engine"]
    assert e == {"max_num_seqs": 46, "kv_block_size": 32,
                 "num_kv_blocks": 12288, "max_model_len": 33792,
                 "prefix_cache": False}
    assert 32768 + 512 <= e["max_model_len"] == CONFIG["program"]["max_seq_len"]
    # the peak lies under the pool
    assert 6 * 33280 + 40 * 2432 == 296960 <= e["num_kv_blocks"] * 32
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: a later PR appends behind these
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == "mellum2-12b-l8")
    assert config["file"].endswith("mellum2-12b-l8.json")
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["source"] == CONFIG["source"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 13 + 3 + 6
    assert "prefix_hit_share" not in listed           # the cache is off
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in new] == NEW
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tokens_per_s"
        mod = __import__(f"benchmark.layer_metrics.{m['name']}",
                         fromlist=["x"])
        assert (mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES) == (
            m["unit"], m["layer"], m["source"], m["moves"])
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in out["workloads"]
    rehearsal = load("workloads", "tiny-repochat-closed.json")
    assert rehearsal["per_layer"] == cell["per_layer"]


# --- the generator ---------------------------------------------------------------


def take(params, seed, client, n):
    s = closed_mixed.stream(params, seed, client)
    return [next(s) for _ in range(n)]


def test_closed_mixed_is_a_pure_function_of_its_arguments():
    seed = 2 ** 31 + 7
    a = take(TRAFFIC, seed, 5, 4)
    assert a == take(TRAFFIC, seed, 5, 4)
    assert a != take(TRAFFIC, seed + 1, 5, 4)
    assert a != take(TRAFFIC, seed, 6, 4)
    b = take(TRAFFIC, seed, 20, 30)
    assert b == take(TRAFFIC, seed, 20, 30) != take(TRAFFIC, seed, 21, 30)


def test_the_two_classes_keep_their_callers_lengths_and_pools():
    seed = 2303000041
    repo = {c: take(TRAFFIC, seed, c, 3) for c in range(6)}
    chat = {c: take(TRAFFIC, seed, c, 10) for c in range(6, 46)}
    for reqs in repo.values():
        assert {r["kind"] for r in reqs} == {"repository"}
        assert all(8192 <= r["prompt_tokens"] <= 32768 for r in reqs)
        assert all(256 <= r["max_tokens"] <= 512 for r in reqs)
    for reqs in chat.values():
        assert {r["kind"] for r in reqs} == {"chat"}
        assert all(64 <= r["prompt_tokens"] <= 2048 for r in reqs)
        assert all(32 <= r["max_tokens"] <= 384 for r in reqs)
    # stratified: the callers of a class deal its pool's quantiles out
    # round-robin, the same multiset whatever the seed
    cls_repo, cls_chat = TRAFFIC["classes"]
    got = sorted(r["prompt_tokens"] for reqs in repo.values() for r in reqs)
    assert got == sorted(_common.stratified_lengths(
        18, cls_repo["prompt_tokens"]).tolist())
    got = sorted(r["max_tokens"] for reqs in chat.values() for r in reqs)
    assert got == sorted(_common.stratified_lengths(
        400, cls_chat["answer_tokens"]).tolist())
    other = sorted(r["prompt_tokens"] for c in range(6)
                   for r in take(TRAFFIC, seed + 9, c, 3))
    assert other == sorted(r["prompt_tokens"] for reqs in repo.values()
                           for r in reqs)
    # every prompt is its own: no two share a first block of 32
    firsts = [r["prompt"][:31] for reqs in (*repo.values(), *chat.values())
              for r in reqs]
    assert len(set(firsts)) == len(firsts)
    lengths = [r["prompt_tokens"] for reqs in chat.values() for r in reqs]
    assert 300 < np.median(lengths) < 480
    with pytest.raises(ValueError):
        closed_mixed.class_of(TRAFFIC, 46)


# --- the runner's seams ----------------------------------------------------------


def test_model_overrides_maps_the_published_keys():
    o = serve_dp_mellum.model_overrides(CONFIG)
    assert (o["dim"], o["n_heads"], o["n_kv_heads"], o["head_dim"]) == (
        2304, 32, 4, 128)
    assert (o["n_layers"], o["layer_ids"], o["full_period"]) == (
        8, tuple(range(8)), 4)
    assert (o["n_experts"], o["top_k"], o["moe_ffn_dim"]) == (64, 8, 896)
    assert (o["sliding_window"], o["rope_theta"], o["yarn_factor"],
            o["yarn_original_len"], o["yarn_attention_factor"]) == (
        1024, 500000.0, 16.0, 8192, 1.2772588722239782)
    from ray_tpu.models import mellum

    cfg = mellum.MellumConfig.mellum2_12b(**o)
    assert cfg.kinds() == ["window"] * 3 + ["full"] + ["window"] * 3 + ["full"]
    assert mellum.MellumConfig.tiny(
        **serve_dp_mellum.model_overrides(TINY)) == mellum.MellumConfig.tiny(
        max_seq_len=128, layer_ids=tuple(range(8)))
    bad = {**CONFIG, "layer_types": ["full_attention"] * 8}
    with pytest.raises(AssertionError):
        serve_dp_mellum.model_overrides(
            {**bad, "program": {**CONFIG["program"], "layer_ids": [0, 1]}})


def gap(**kw):
    base = {"gaps": [0.01], "max_abs_logit": 4.0, "argmax_equal": 1,
            "replay_equal": True, "prompt_tokens": 100,
            "routing": {"expert_steps": 3.0, "same_experts": 0.99},
            "router_f32_steps": 2.0, "router_f32_steps_bf16": 9000.0,
            "attn_window_error": 0.01, "attn_full_error": 0.012}
    return {**base, **kw}


@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    ({"replay_equal": False}, False),
    ({"routing": {"expert_steps": 41.0, "same_experts": 0.9}}, False),
    ({"router_f32_steps": 9000.0}, False),
    ({"attn_window_error": 0.3}, False),
    ({"attn_full_error": 0.3}, False),
    ({"gaps": [0.2]}, False),
])
def test_judge_check_holds_every_limit(fault, ok):
    check = serve_dp_mellum.judge_check(
        [gap(), gap(**fault)], serve_dp_mellum.CHECK_TOLERANCE_BF16_STEPS)
    assert check["ok"] is ok
    assert check["router_f32_steps_bf16"] == 9000.0


def test_judge_check_says_what_a_plant_fails_by():
    plant = {"gaps": [0.5], "max_abs_logit": 4.0, "expert_steps": 3.0,
             "attn_window_error": 0.4, "attn_full_error": 0.01}
    check = serve_dp_mellum.judge_check(
        [gap(plants={"window_layers_full": plant})], 8.0)
    assert check["ok"] is True
    assert check["plants"]["window_layers_full"]["fails_by"] == [
        "gap_steps", "attn_window_error"]


def test_run_puts_the_seams_back(monkeypatch):
    before = (serve_dp.model_overrides, serve_dp.sum_stats,
              serve_dp.judge_check, serve_dp.CHECK_TOLERANCE_BF16_STEPS)
    monkeypatch.setattr(serve_dp, "run", lambda ctx: {"ran": (
        serve_dp.model_overrides is serve_dp_mellum.model_overrides)})

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, False, "/nowhere"

    art = serve_dp_mellum.run(Ctx)
    assert art["ran"] and art["config"]["layer_ids"] == list(range(8))
    assert before == (serve_dp.model_overrides, serve_dp.sum_stats,
                      serve_dp.judge_check, serve_dp.CHECK_TOLERANCE_BF16_STEPS)


# --- bytes, operations and the readers ------------------------------------------


def test_weight_bytes_are_the_configuration_files():
    w = bytes_mellum.weight_bytes(CONFIG)
    b = bytes_mellum.block_params(CONFIG)
    assert b["attn"] == 2304 * (4096 + 2 * 512) + 4096 * 2304
    assert b["expert"] == 3 * 2304 * 896
    assert w["expert"] == 2 * b["expert"]
    # everything held: the issue's 3.795B parameters, the routers in float32
    held = 2 * 3_794_968_832 + 2 * 8 * b["router"]
    assert abs(w["held"] - held) < 1e5
    # a step that touches every expert reads ~7.1 GB of weights
    assert 7.0e9 < w["fixed"] + 8 * 64 * w["expert"] < 7.2e9
    assert bytes_mellum.position_bytes(CONFIG) == 2048


def counters(**kw):
    d = dict(steps=100.0, steps_with_chunk=90.0, prefill_chunk_tokens=20000.0,
             moe_pairs_routed=(20000 + 4800) * 8 * 8.0,
             moe_pairs_held=(20000 + 4800) * 8 * 8.0,
             moe_experts_touched=100 * 8 * 60.0,
             attn_positions_live=100 * 300000.0,
             kv_positions_live=2 * 100 * 300000.0,
             window_positions=6 * 100 * 45000.0,
             chunk_keys_read=90 * (2 * 16000.0 + 6 * 1279),
             chunk_pairs=90 * 256 * (2 * 16000.0 + 6 * 1024))
    return {**d, **kw}


def test_step_bytes_and_flops_from_the_counters():
    d = counters()
    rows = bytes_mellum.rows_of(CONFIG, d)
    assert rows == {"rows": 24800.0, "chunk_rows": 20000.0,
                    "decode_rows": 4800.0}
    need = bytes_mellum.step_bytes(CONFIG, d)
    w = bytes_mellum.weight_bytes(CONFIG)
    assert need["experts"] == 48000 * w["expert"]
    assert need["kv"] == (d["kv_positions_live"] + d["window_positions"]
                          + d["chunk_keys_read"]) * 2048
    assert need["total"] == sum(v for k, v in need.items() if k != "total")
    did = bytes_mellum.step_flops(CONFIG, d)
    assert did["experts"] == 2.0 * d["moe_pairs_held"] * 3 * 2304 * 896
    assert did["head"] == 2.0 * (4800 + 90) * 2304 * 98304
    assert did["attention"] == 4 * 32 * 128 * (
        d["kv_positions_live"] + d["window_positions"] + d["chunk_pairs"])


def art_of(d, **extra):
    zero = {k: 0.0 for k in bytes_mellum.COUNTERS}
    return {"config": serve_dp_mellum.reference_hp(CONFIG),
            "stats_open": zero, "stats_close": d,
            "device": {"kind": "TPU v5 lite"}, **extra}


def test_the_readers_read_the_counters_and_find_nothing_on_a_parent():
    d = counters()
    assert window_positions_share.read(art_of(d)) == pytest.approx(
        100.0 * 45000 / 300000)
    # a program without the counters (any parent): nothing, and no raise
    for reader in (window_positions_share, mellum_decode_attention_roofline,
                   mellum_step_hbm_roofline, mellum_step_mfu):
        assert reader.read({"stats_open": {"steps": 0}, "stats_close":
                            {"steps": 5}, "config": CONFIG}) is None
        assert reader.read({}) is None
    # the kernel's calls against the bytes of the live positions alone
    per_call = (d["kv_positions_live"] + d["window_positions"]) * 2048 / 100 / 8
    calls = [("%paged_decode_attention.3 = ...", 0, 400_000.0)] * 16
    art = art_of(d, trace={"pallas_events": calls + [("%grouped_ffn", 0, 9.0)]})
    got = mellum_decode_attention_roofline.read(art)
    assert got == pytest.approx(100.0 * per_call / 819e9 / 400e-6, rel=1e-6)
    assert 0 < got < 100


# --- the reference at the tiny size against the family ---------------------------


def test_the_reference_agrees_with_the_family_at_the_tiny_size():
    import jax
    import jax.numpy as jnp

    from benchmark.runners._inside_mellum import ProgramWeightsMellum
    from ray_tpu.models import mellum

    cfg = mellum.MellumConfig.tiny(**serve_dp_mellum.model_overrides(TINY))
    params = mellum.init_params(cfg, jax.random.PRNGKey(2))
    weights = ProgramWeightsMellum(params, 2 * 16)
    toks = [int(t) for t in np.random.default_rng(4).integers(0, 512, 90)]
    padded = np.zeros(128, np.int32)
    padded[:90] = toks
    got = jax.jit(lambda t: mellum.forward(cfg, params, t, 90))(
        jnp.asarray(padded))
    want = reference_mellum.logits_at(TINY, weights, toks, list(range(90)))
    np.testing.assert_allclose(np.asarray(got)[:90], want, atol=2e-4)
    for name, plant in reference_mellum.PLANTS.items():
        low = reference_mellum.logits_at(TINY, weights, toks, list(range(90)),
                                         **plant)
        assert np.abs(low - want).max() > 0.02, name


# --- the rehearsal twin ----------------------------------------------------------


@pytest.mark.parametrize("trace,seed", [(0, 5), (1, 2 ** 31 + 11)])
def test_the_rehearsal_cell_runs_on_the_cpu(trace, seed):
    proc = run_cell("tiny-repochat-closed", trace, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {}
    log = [ln for ln in proc.stderr.splitlines() if ln.startswith("[bench")]
    check = next(ln for ln in log if "reference check" in ln)
    assert "'replays_equal': True" in check
    # the planted departures the rehearsal's traffic names read over a limit
    assert "'window_layers_full'" in check and "'fails_by': []" not in check
    summary = json.loads(next(
        ln for ln in log if "summary: " in ln).split("summary: ", 1)[1])
    a, b = summary["stats_open"], summary["stats_close"]
    assert b["window_bytes"] == a["window_bytes"] > 0
    assert b["window_positions"] > a["window_positions"]
    assert b["chunk_pairs"] > a["chunk_pairs"]

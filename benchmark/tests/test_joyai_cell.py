"""The JoyAI-LLM-Flash cell's own pieces on the CPU: the configuration file
against the catalog's numbers, the traffic generator, the runner's seams, the
byte, operation and counter readers, the reference at the tiny size against
the family, planted faults that the check must refuse, and the rehearsal twin
end to end.

    python -m pytest benchmark/tests/test_joyai_cell.py -q        (not part of tier-1)
"""

import asyncio
import json
import os
import types

import numpy as np
import pytest

from benchmark.layer_metrics import (joyai_latent_attention_roofline,
                                     joyai_step_hbm_roofline, joyai_step_mfu,
                                     latent_shared_read_share)
from benchmark.lib import bytes_joyai, reference_joyai, scopes
from benchmark.runners import _inside_joyai, serve_dp, serve_dp_joyai
from benchmark.tests.test_rehearsal import RESULT_KEYS, ROOT, load, run_cell
from benchmark.traffic import _common, closed_agent_turns

CONFIG = load("configs", "joyai-llm-flash-ep16.json")
TINY = load("configs", "tiny-joyai.json")
TRAFFIC = load("traffic", "agentturns-closed.json")
TINY_TRAFFIC = load("traffic", "tiny-agentturns-closed.json")
CELL = "joyai-agentturns-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["joyai_latent_attention_roofline", "joyai_step_hbm_roofline",
       "joyai_step_mfu", "latent_shared_read_share"]
HP = serve_dp_joyai.reference_hp(CONFIG)


def test_the_configuration_keeps_every_published_number_but_the_two_cuts():
    assert set(CONFIG["reduced"]) == {"n_routed_experts",
                                      "num_nextn_predict_layers"}
    cut = CONFIG["reduced"]
    assert (cut["n_routed_experts"]["published"],
            cut["n_routed_experts"]["here"], CONFIG["n_routed_experts"]) == (
        256, 16, 16)
    assert (cut["num_nextn_predict_layers"]["published"],
            cut["num_nextn_predict_layers"]["here"],
            CONFIG["num_nextn_predict_layers"]) == (1, 0, 0)
    prog = CONFIG["program"]
    assert (prog["router_num_experts"], prog["held_experts_start"]) == (256, 0)
    kept = {"hidden_size": 2048, "num_hidden_layers": 40,
            "num_attention_heads": 32, "q_lora_rank": 1536,
            "kv_lora_rank": 512, "qk_nope_head_dim": 128,
            "qk_rope_head_dim": 64, "v_head_dim": 128,
            "intermediate_size": 7168, "moe_intermediate_size": 768,
            "num_experts_per_tok": 8, "vocab_size": 129280,
            "rope_theta": 32000000, "first_k_dense_replace": 1}
    assert {k: CONFIG[k] for k in kept} == kept
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "JoyAI-LLM-Flash")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k, "?") != v}
        assert differ == set(CONFIG["reduced"])
    assumed = " ".join(CONFIG["assumed"])
    with open(os.path.join(ROOT, "tests", "test_joyai.py")) as f:
        tier1 = f.read()
    for test in ("test_assumed_low_rank_query_is_normed",
                 "test_assumed_rotary_in_adjacent_pairs",
                 "test_assumed_ungrouped_sigmoid_routing",
                 "test_assumed_expert_bias_is_balanced",
                 "test_sixteen_shares_add_up_to_the_uncut_layer"):
        assert test in assumed and f"def {test}(" in tier1
    for word in ("deployment", "bytes", "engine_note"):
        assert CONFIG[word]
    assert "16 chips" in CONFIG["deployment"]
    for number in ("9.55 GB", "51.2 KB", "5.03 GB"):
        assert number in CONFIG["bytes"]


def test_the_cell_and_its_traffic_are_the_issues_to_the_number():
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash-ep16", "agentturns-closed", 1)
    assert cell["end_to_end"] == ["out_tokens_per_s", "setup_s"]
    assert len(cell["why"]) <= 200
    t = TRAFFIC
    assert (t["generator"], t["clients"], t["tenants"], t["system_tokens"],
            t["turns"], t["pool"], t["ramp_s"], t["trace_s"]) == (
        "closed_agent_turns", 16, 4, 8192, 6, 96, 30, 1)
    # ISSUE 60's one allowed change (384-896) was tried and is not taken: it
    # did not narrow the spread (PERF.md section 6)
    assert t["piece_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert t["answer_tokens"] == {"dist": "uniform", "min": 64, "max": 192}
    assert t["check"]["session_of_client"] == 16 and t["check"]["requests"] == 6
    assert t["check"]["second_readings"] is False
    # the warm-up's sessions: one a tenant, the check's first
    assert [c % 4 for c in t["warm"]["sessions_of_clients"]] == [0, 1, 2, 3]
    assert all(c >= 16 for c in t["warm"]["sessions_of_clients"])
    e = CONFIG["engine"]
    assert e == {"max_num_seqs": 16, "kv_block_size": 16,
                 "num_kv_blocks": e["num_kv_blocks"], "max_model_len": 16384,
                 "prefix_cache": True}
    assert 5120 <= e["num_kv_blocks"] <= 6144
    assert 8192 + 6 * 1024 + 192 <= e["max_model_len"] == CONFIG[
        "program"]["max_seq_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: a later PR appends behind these
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "joyai-llm-flash-ep16")
    assert config["file"].endswith("joyai-llm-flash-ep16.json")
    assert config["reduced"] == ["n_routed_experts", "num_nextn_predict_layers"]
    assert config["source"] == CONFIG["source"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 19 + 4
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
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    rehearsal = load("workloads", "tiny-agentturns-closed.json")
    assert rehearsal["per_layer"] == cell["per_layer"]


# --- the generator ---------------------------------------------------------------


def take(params, seed, client, n):
    s = closed_agent_turns.stream(params, seed, client)
    return [next(s) for _ in range(n)]


def test_closed_agent_turns_is_a_pure_function_of_its_arguments():
    seed = 2 ** 31 + 7
    a = take(TRAFFIC, seed, 5, 4)
    assert a == take(TRAFFIC, seed, 5, 4)
    assert a != take(TRAFFIC, seed + 1, 5, 4)
    assert a != take(TRAFFIC, seed, 6, 4)


def test_sessions_share_a_system_prompt_and_their_own_history_alone():
    seed = 2303000041
    first = {c: take(TRAFFIC, seed, c, 13) for c in range(16)}
    system = {t: closed_agent_turns.system_prompt(TRAFFIC, seed, t)
              for t in range(4)}
    assert len({s[:64] for s in system.values()}) == 4
    for c, reqs in first.items():
        assert all(len(system[c % 4]) + 1 == 8192 for _ in reqs)
        # session i's first conversation starts at turn i mod 6
        assert [r["turn"] for r in reqs][: 6 - c % 6] == list(range(c % 6, 6))
        assert reqs[6 - c % 6]["turn"] == 0 and reqs[6 - c % 6][
            "conversation"] == 1
        for a, b in zip(reqs, reqs[1:]):
            assert b["prompt"].startswith(system[c % 4])
            if b["turn"]:
                # the next turn is the last prompt and one more piece
                assert b["prompt"].startswith(a["prompt"])
                piece = len(b["prompt"]) - len(a["prompt"])
                assert 256 <= piece <= 1024
            assert 64 <= b["max_tokens"] <= 192
            assert 8192 + 256 <= b["prompt_tokens"] <= 8192 + 6 * 1024
    # nothing else is shared: no piece opens as another does
    pieces = set()
    for c, reqs in first.items():
        for r in reqs:
            tail = r["prompt"][8191:]
            marks = [tail[i:i + 24] for i in range(len(tail))
                     if tail.startswith(" [", i)]
            pieces.update((c, r["conversation"], m) for m in marks)
    assert len({m for _, _, m in pieces}) == len(
        {(c, v, m) for c, v, m in pieces})
    # stratified: a round of conversations sends the whole pool once
    whole = sorted(_common.stratified_lengths(96, TRAFFIC["answer_tokens"]))
    for conv in (0, 1):
        got = []
        for c in range(16):
            reqs = take(TRAFFIC, seed, c, 18)
            got += [r["max_tokens"] for r in reqs
                    if r["conversation"] == conv + 1]
        assert sorted(got) == whole
    other = take(TRAFFIC, seed + 9, 3, 6)
    assert sorted(r["turn"] for r in other) == sorted(
        r["turn"] for r in first[3][:6])
    # a check's or a warm-up's session starts at its first turn
    assert [r["turn"] for r in take(TRAFFIC, seed, 16, 6)] == list(range(6))
    assert take(TRAFFIC, seed, 20, 1)[0]["prompt"].startswith(system[0])


# --- the runner's seams ----------------------------------------------------------


def test_model_overrides_maps_the_published_keys():
    from ray_tpu.models import joyai

    o = serve_dp_joyai.model_overrides(CONFIG)
    cfg = joyai.JoyAIConfig.joyai_llm_flash(**o)
    assert cfg == joyai.JoyAIConfig(n_held=16)
    assert (cfg.n_experts, cfg.n_held, cfg.held_start, cfg.top_k) == (
        256, 16, 0, 8)
    assert joyai.JoyAIConfig.tiny(
        **serve_dp_joyai.model_overrides(TINY)) == joyai.JoyAIConfig.tiny(
        n_held=8, held_start=4)
    with pytest.raises(AssertionError):
        serve_dp_joyai.model_overrides({**CONFIG, "n_group": 8})
    with pytest.raises(AssertionError):
        serve_dp_joyai.model_overrides({**CONFIG, "scoring_func": "softmax"})


def test_the_warm_up_leaves_every_tenants_system_prompt():
    warm = serve_dp_joyai.warm_requests(TRAFFIC, 77)
    check = serve_dp.check_requests(closed_agent_turns, TRAFFIC, 77)
    assert len(warm) == 4 and {r["max_tokens"] for r in warm} == {2}
    assert [r["prompt"][:8191] for r in warm] == [
        closed_agent_turns.system_prompt(TRAFFIC, 77, t) for t in range(4)]
    assert len(check) == 6 and {r["max_tokens"] for r in check} == {32}
    assert warm[0]["prompt"][:8191] == check[0]["prompt"][:8191]
    assert warm[0]["prompt"][8191:8230] != check[0]["prompt"][8191:8230]


def gap(**kw):
    base = {"gaps": [0.01], "max_abs_logit": 4.0, "argmax_equal": 1,
            "replays_part_at": [-1, -1, -1], "prompt_tokens": [100, 150, 200],
            "routing": {"expert_steps": 3.0, "same_experts": 0.99},
            "router_f32_steps": 2.0, "router_f32_steps_bf16": 0.0,
            "cache_error": 0.004, "cache_row_error": 0.03,
            "cache_error_first": 0.003, "cache_error_float8": 0.0,
            "cache_error_first_float8": 0.0, "cache_worst_layer": 39,
            "cached_positions": 192, "cached_positions_least": 192,
            "block_hits": 30, "block_hits_least": 28,
            "served_tokens_judged": 34,
            "seconds": {"replay": 1.0, "reference": 2.0}}
    return {**base, **kw}


@pytest.mark.parametrize("fault,ok", [
    ({}, True),
    # where a replay parts is reported, not held
    ({"replays_part_at": [3, 2, 7]}, True),
    ({"routing": {"expert_steps": 41.0, "same_experts": 0.9}}, False),
    ({"router_f32_steps": 9000.0}, False),
    ({"cache_error": 0.05}, False),
    ({"cache_error_first": 0.02}, False),
    ({"cache_row_error": 1.2}, False),
    ({"block_hits": 27}, False),
    ({"cached_positions": 176}, False),
    ({"gaps": [0.2]}, False),
])
def test_judge_check_holds_every_limit(fault, ok):
    check = serve_dp_joyai.judge_check(
        [gap(**fault)], serve_dp_joyai.CHECK_TOLERANCE_BF16_STEPS)
    assert check["ok"] is ok
    assert "second_readings" not in check
    low = serve_dp_joyai.judge_check(
        [gap(gaps_float8=[0.9], cache_error_float8=0.04,
             cache_error_first_float8=0.026, expert_steps_float8=300.0,
             router_f32_steps_bf16=7000.0)], 8.0)
    assert low["second_readings"] == {
        "gap_steps_float8": pytest.approx(0.9 / (4.0 * 2 ** -8)),
        "expert_steps_float8": 300.0, "router_f32_steps_bf16": 7000.0,
        "cache_error_float8": 0.04, "cache_error_first_float8": 0.026}


def test_run_puts_the_seams_back(monkeypatch):
    from benchmark.runners import _inside

    before = (serve_dp.model_overrides, serve_dp.sum_stats,
              serve_dp.warm_requests, serve_dp.judge_check,
              serve_dp.CHECK_TOLERANCE_BF16_STEPS,
              _inside.engine_reference_check)
    seen = {}

    def fake_run(ctx):
        seen["check"] = _inside.engine_reference_check
        return {"ran": serve_dp.warm_requests is serve_dp_joyai.warm_requests}

    monkeypatch.setattr(serve_dp, "run", fake_run)

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, True, "/nowhere"

    art = serve_dp_joyai.run(Ctx)
    assert art["ran"] and art["config"]["router_num_experts"] == 256
    assert seen["check"].keywords["scopes_path"] == "/nowhere/scopes.json"
    assert seen["check"].keywords["system_tokens"] == 8192
    assert before == (serve_dp.model_overrides, serve_dp.sum_stats,
                      serve_dp.warm_requests, serve_dp.judge_check,
                      serve_dp.CHECK_TOLERANCE_BF16_STEPS,
                      _inside.engine_reference_check)


# --- bytes, operations and the readers ------------------------------------------


def test_weight_bytes_are_the_configuration_files():
    w, b = bytes_joyai.weight_bytes(HP), bytes_joyai.block_params(HP)
    assert b["attn"] == (3_145_728 + 9_437_184 + 1_179_648 + 4_194_304
                         + 8_388_608)
    assert b["expert"] == b["shared"] == 4_718_592 and b["router"] == 524_288
    # the issue's 4,776.5M parameters and 9.55 GB (the routers in float32)
    assert w["params"] == 4_776_521_472
    assert abs(w["held"] - (2 * w["params"] + 2 * 39 * (b["router"] + 256))
               ) < 1e5
    assert 9.55e9 < w["held"] < 9.60e9
    # 1,280 B a position and layer, 51.2 KB a position
    assert bytes_joyai.position_bytes(HP) == 1280
    assert 40 * bytes_joyai.position_bytes(HP) == 51_200
    # a step that touches 6 held experts a layer reads ~5.4 GB of weights
    assert 5.2e9 < w["fixed"] + 39 * 6 * w["expert"] < 5.6e9


def counters(**kw):
    rows = 100 * 16 + 3 * 256
    d = dict(steps=100.0, steps_with_chunk=3.0, prefill_chunk_tokens=768.0,
             moe_pairs_routed=rows * 8 * 39.0, moe_pairs_held=rows * 39 / 2.0,
             moe_experts_touched=100 * 39 * 6.0,
             latent_positions_read=100 * 40 * 16 * 10500.0,
             chunk_latents_read=3 * 40 * 12000.0,
             attn_positions_live=100 * 16 * 10500.0,
             attn_positions_shared=100 * 16 * 8192.0)
    return {**d, **kw}


def test_step_bytes_and_flops_from_the_counters():
    d = counters()
    assert bytes_joyai.rows_of(HP, d) == {
        "rows": 2368.0, "chunk_rows": 768.0, "decode_rows": 1600.0}
    need = bytes_joyai.step_bytes(HP, d)
    w = bytes_joyai.weight_bytes(HP)
    assert need["experts"] == 100 * 39 * 6 * w["expert"]
    assert need["latents"] == (d["latent_positions_read"]
                               + d["chunk_latents_read"]) * 1280
    # 16 rows of ~10.5k latents through 40 layers: 8.6 GB a step
    assert 8.5e9 < d["latent_positions_read"] * 1280 / 100 < 8.7e9
    assert need["total"] == sum(v for k, v in need.items() if k != "total")
    did = bytes_joyai.step_flops(HP, d)
    assert did["experts"] == 2.0 * d["moe_pairs_held"] * 3 * 2048 * 768
    assert did["head"] == 2.0 * (1600 + 3) * 2048 * 129280
    pairs = 256 * 3 * 40 * 12000.0 - 40 * 3 * 256 * 255 / 2.0
    assert bytes_joyai.chunk_pairs(HP, d) == pairs
    assert did["attention"] == 2 * 32 * 320 * (
        d["latent_positions_read"] + pairs)


def art_of(d, **extra):
    zero = {k: 0.0 for k in d}
    return {"config": HP, "stats_open": zero, "stats_close": d,
            "device": {"kind": "TPU v5 lite"}, **extra}


def test_the_readers_read_the_counters_and_find_nothing_on_a_parent():
    d = counters()
    assert latent_shared_read_share.read(art_of(d)) == pytest.approx(
        100.0 * 8192 / 10500)
    # a program without the counters (any parent): nothing, and no raise
    for reader in (latent_shared_read_share, joyai_latent_attention_roofline,
                   joyai_step_hbm_roofline, joyai_step_mfu):
        assert reader.read({"stats_open": {"steps": 0}, "stats_close":
                            {"steps": 5}, "config": CONFIG}) is None
        assert reader.read({}) is None
    # the kernel's calls against each live latent read once
    per_call = d["latent_positions_read"] * 1280 / 100 / 40
    calls = [("%paged_decode_attention.3 = ...", 0, 600_000.0)] * 80
    art = art_of(d, trace={"pallas_events": calls + [("%grouped_ffn", 0, 9.0)]})
    got = joyai_latent_attention_roofline.read(art)
    assert got == pytest.approx(100.0 * per_call / 819e9 / 600e-6, rel=1e-6)
    assert 0 < got < 100


HLO = """HloModule jit_paged_decode_step
ENTRY %main {
  %fusion.2 = bf16[272,512]{1,0} fusion(%b), kind=kLoop, calls=%fc2, metadata={op_name="jit(paged_decode_step)/while/body/mla/dot_general"}
  %fusion.3 = bf16[272,2048]{1,0} fusion(%c), kind=kLoop, calls=%fc3, metadata={op_name="jit(paged_decode_step)/while/body/moe/dot_general"}
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc4, metadata={op_name="jit(paged_decode_step)/rsqrt"}
}
"""


def test_mla_moe_and_the_rest_add_up_to_the_busy_time():
    found = scopes.instruction_scopes([HLO])
    assert found == {"fusion.2 bf16[272,512]": "mla",
                     "fusion.3 bf16[272,2048]": "moe"}
    trace = {"/device:TPU:0": {
        "XLA Modules": [("jit_paged_decode_step(1)", 0.0, 100.0)],
        "XLA Ops": [
            ("%fusion.2 = bf16[272,512]{1,0} fusion(%b), kind=kLoop", 0.0, 60.0),
            ("%fusion.3 = bf16[272,2048]{1,0} fusion(%c), kind=kLoop", 60.0, 25.0),
            ("%fusion.7 = f32[8]{0} fusion(%a)", 85.0, 15.0)]}}
    times = scopes.scope_times(trace, {"jit_paged_decode_step": found}, 0, 100)
    assert times == pytest.approx(
        {"kda": 0.0, "mla": 60e-9, "moe": 25e-9, "rest": 15e-9})
    assert sum(times.values()) == pytest.approx(100e-9)


# --- the reference and the check at the tiny size --------------------------------


def tiny_engine(**overrides):
    import jax

    from ray_tpu.llm._engine import EngineConfig, PagedEngine
    from ray_tpu.models import joyai

    cfg = joyai.JoyAIConfig.tiny(**{**serve_dp_joyai.model_overrides(TINY),
                                    **overrides})
    params = jax.jit(lambda k: joyai.seeded_params(cfg, k))(
        jax.random.PRNGKey(4))
    return PagedEngine(cfg, params, EngineConfig(**TINY["engine"]))


def test_the_reference_agrees_with_the_family_at_the_tiny_size():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import joyai

    engine = tiny_engine()
    weights = _inside_joyai.ProgramWeightsJoyAI(engine.params)
    toks = [int(t) for t in np.random.default_rng(4).integers(0, 512, 90)]
    padded = np.zeros(128, np.int32)
    padded[:90] = toks
    got = jax.jit(lambda t: joyai.forward(engine.cfg, engine.params, t, 90))(
        jnp.asarray(padded))
    hp = serve_dp_joyai.reference_hp(TINY)
    want = reference_joyai.logits_at(hp, weights, list(padded), list(range(90)))
    np.testing.assert_allclose(np.asarray(got)[:90], want, atol=2e-4)


def checked_session(engine, seed=9, spoil=None):
    """The runner's check on an engine in this process: the warm-up request,
    the check session's turns one after the other (as `serve_dp.drive` sends
    them, without the HTTP plane), then `engine_reference_check` and
    `judge_check`. `spoil(engine)` runs between the warm-up and the session."""
    from ray_tpu.llm import BOS

    async def ask(req):
        ids = [BOS] + list(req["prompt"].encode())
        out = [t async for t in engine.generate_stream(
            ids, max_tokens=req["max_tokens"])]
        return {"prompt_ids": ids, "answer_ids": out}

    async def run():
        traffic = {**TINY_TRAFFIC, "kv_block_size": 16}
        for req in serve_dp_joyai.warm_requests(traffic, seed):
            await ask(req)
        if spoil is not None:
            spoil(engine)
        samples = [await ask(r) for r in serve_dp.check_requests(
            closed_agent_turns, traffic, seed)]
        return await _inside_joyai.engine_reference_check(
            types.SimpleNamespace(engine=engine),
            serve_dp.published(TINY), samples, 64,
            config=serve_dp_joyai.reference_hp(TINY), system_tokens=64)

    gaps = asyncio.run(run())
    return serve_dp_joyai.judge_check(
        gaps, serve_dp_joyai.CHECK_TOLERANCE_BF16_STEPS)


def over(check):
    """The limits a check reads over, by name."""
    limits = {**serve_dp_joyai.ROUTER_TOLERANCE_STEPS,
              **serve_dp_joyai.MECHANISM_LIMITS,
              "worst_gap_bf16_steps": check["tolerance_steps"]}
    return {k for k, v in limits.items() if check[k] > v}


def test_a_sound_engine_passes_the_check():
    check = checked_session(tiny_engine())
    assert check["ok"] is True and over(check) == set()
    assert check["replays_part_at"] == [-1, -1, -1]
    assert check["block_hits"] >= check["block_hits_least"] > 0
    assert check["cache_error"] < 1e-5


def chunk_skips_the_cached_blocks(monkeypatch):
    """A chunk reads the trash block in the place of every block before its
    own first: what another sequence's prompt left is not attended."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention

    real = paged_attention.chunk_latent_attention

    def skipping(q, pool, layer, row, qpos, end, rank, **kw):
        own = jnp.arange(row.shape[0]) >= qpos[0] // pool.shape[2]
        return real(q, pool, layer, jnp.where(own, row, 0), qpos, end, rank,
                    **kw)

    monkeypatch.setattr(paged_attention, "chunk_latent_attention", skipping)
    return {}


def latents_in_float8(monkeypatch):
    from ray_tpu.models import ling

    real = ling.mla_latents

    def rounded(cfg, p, x, positions):
        import jax.numpy as jnp

        lat = real(cfg, p, x, positions)
        return lat.astype(jnp.float8_e4m3fn).astype(lat.dtype)

    monkeypatch.setattr(ling, "mla_latents", rounded)
    return {}


def query_norm_dropped(monkeypatch):
    from ray_tpu.models import ling

    def q_without_its_norm(cfg, p, x, positions):
        q = (x @ p["wqa"] @ p["wqb"]).reshape(
            x.shape[0], cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
        q_r = ling.rope_pairs(q[..., cfg.qk_nope_dim:], positions,
                              cfg.rope_theta)
        return q[..., : cfg.qk_nope_dim], q_r.astype(cfg.dtype)

    monkeypatch.setattr(ling, "_mla_q", q_without_its_norm)
    return {}


def rotary_in_halves(monkeypatch):
    return {"rope_interleave": False}


def stale_shared_block(engine, seed=9):
    """Block 3 of the check's tenant's system prompt, which the warm-up left,
    holds block 1's rows."""
    from ray_tpu.llm import BOS
    from ray_tpu.llm._prefix_cache import chain_keys

    system = closed_agent_turns.system_prompt(TINY_TRAFFIC, seed, 0)
    keys = chain_keys([BOS] + list(system.encode()), 16)
    entries = engine._prefix_cache._entries
    a, b = entries[keys[1]].block, entries[keys[3]].block
    engine.latents = engine.latents.at[:, b].set(engine.latents[:, a])


@pytest.mark.parametrize("plant,fails_by", [
    (chunk_skips_the_cached_blocks, "cache_error"),
    (latents_in_float8, "cache_error"),
    (query_norm_dropped, "expert_steps"),
    (rotary_in_halves, "cache_error"),
])
def test_a_planted_fault_in_the_program_fails_the_limit_named_for_it(
        monkeypatch, plant, fails_by):
    check = checked_session(tiny_engine(**plant(monkeypatch)))
    assert check["ok"] is False and fails_by in over(check), check


def test_a_stale_shared_block_fails_the_cache_row_limit():
    check = checked_session(tiny_engine(), spoil=stale_shared_block)
    assert check["ok"] is False
    assert "cache_row_error" in over(check), check
    # the session was served from it: the logits part too
    assert check["worst_gap_bf16_steps"] > 1.0


# --- the rehearsal twin ----------------------------------------------------------


@pytest.mark.parametrize("trace,seed", [(0, 5), (1, 2 ** 31 + 11)])
def test_the_rehearsal_cell_runs_on_the_cpu(trace, seed):
    proc = run_cell("tiny-agentturns-closed", trace, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {}
    log = [ln for ln in proc.stderr.splitlines() if ln.startswith("[bench")]
    check = next(ln for ln in log if "reference check" in ln)
    assert "'replays_equal': True" in check and "'second_readings'" in check
    summary = json.loads(next(
        ln for ln in log if "summary: " in ln).split("summary: ", 1)[1])
    a, b = summary["stats_open"], summary["stats_close"]
    assert b["latent_bytes"] == a["latent_bytes"] > 0
    assert b["latent_positions_read"] > a["latent_positions_read"]
    assert b["chunk_latents_read"] > a["chunk_latents_read"]
    assert b["attn_positions_shared"] > a["attn_positions_shared"]
    assert b["prefix_cache"]["block_hits"] > a["prefix_cache"]["block_hits"]
    if trace:
        with open(os.path.join(ROOT, ".bench_out", "tiny-agentturns-closed",
                               "scopes.json")) as f:
            found = json.load(f)["jit_paged_decode_step"]
        assert {"mla", "moe"} <= set(found.values())

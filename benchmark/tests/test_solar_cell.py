"""The Solar-Open2 cell's own pieces on the CPU: the configuration file
against the catalog's numbers, the runner's seams, the byte, operation, scope
and counter readers, planted faults that the check must refuse, and the
rehearsal twin end to end.

    python -m pytest benchmark/tests/test_solar_cell.py -q        (not part of tier-1)
"""

import asyncio
import json
import os
import types

import numpy as np
import pytest

from benchmark.layer_metrics import (chunk_step_share, gqa_device_share,
                                     moe_held_pair_share,
                                     moe_load_max_over_mean,
                                     snapshot_rerun_share,
                                     solar_step_hbm_roofline, solar_step_mfu)
from benchmark.lib import bytes_solar, reference_solar as ref, scopes
from benchmark.lib import scopes_solar
from benchmark.runners import _inside, _inside_solar, serve_dp, serve_dp_solar
from benchmark.tests.test_rehearsal import RESULT_KEYS, ROOT, load, run_cell

CONFIG = load("configs", "solar-open2-250b-l4-ep8.json")
TRAFFIC = load("traffic", "longdocqa-closed.json")
CELL = "solaropen2-longdocqa-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["chunk_step_share", "snapshot_rerun_share", "gqa_device_share",
       "solar_step_hbm_roofline", "solar_step_mfu"]


def test_the_configuration_keeps_every_published_number_but_the_three_cuts():
    cut = {"num_hidden_layers": (48, 4), "n_routed_experts": (320, 40),
           "vocab_size": (196608, 24576)}
    assert set(CONFIG["reduced"]) == set(cut)
    for key, (published, here) in cut.items():
        assert CONFIG["reduced"][key]["published"] == published
        assert CONFIG["reduced"][key]["here"] == CONFIG[key] == here
    kept = {"hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
            "num_key_value_heads": 8, "moe_intermediate_size": 1280,
            "intermediate_size": 10240, "num_experts_per_tok": 8,
            "routed_scaling_factor": 1, "gqa_interval": 3,
            "rms_norm_eps": 1e-05, "first_k_dense_replace": 0,
            "n_shared_experts": 1, "max_position_embeddings": 1048576}
    assert {k: CONFIG[k] for k in kept} == kept
    assert CONFIG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Solar-Open2-250B")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == set(cut)
    # one whole period, GQA first; the floors of the model-configs guide
    assert ref.layer_kinds(serve_dp_solar.reference_hp(CONFIG)) == [
        "gqa", "kda", "kda", "kda"]
    assert CONFIG["n_routed_experts"] >= CONFIG["num_experts_per_tok"]
    assert CONFIG["vocab_size"] * 8 >= 196608
    assert len(CONFIG["assumed"]) >= 10 and CONFIG["deployment"]


def test_the_cell_and_its_traffic_are_the_issues_to_the_number():
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-l4-ep8", "longdocqa-closed", 1)
    assert cell["end_to_end"] == ["out_tokens_per_s", "setup_s"]
    t = TRAFFIC
    assert (t["generator"], t["clients"], t["pool"], t["ramp_s"]) == (
        "closed_sessions", 16, 128, 30)
    assert t["document_tokens"] == {"dist": "uniform", "min": 8192,
                                    "max": 16384}
    assert t["question_tokens"] == {"dist": "uniform", "min": 64, "max": 256}
    assert (t["questions_per_session"], t["answer_tokens"]) == (4, 128)
    assert t["check"]["document_tokens"] == 8192 and t["check"]["requests"] == 4
    e = CONFIG["engine"]
    assert e == {"max_num_seqs": 16, "kv_block_size": 16,
                 "num_kv_blocks": 32768, "max_model_len": 17408,
                 "prefix_cache": True, "num_state_snapshots": 64}
    assert 16384 + 256 + 128 <= e["max_model_len"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: later PRs append behind these
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    config = next(c for c in bench["configs"]
                  if c["name"] == "solar-open2-250b-l4-ep8")
    assert config["file"].endswith("solar-open2-250b-l4-ep8.json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 23
    # the five readers the cell brought (PR 46); later cells list the
    # chunks' and the snapshots' beside their own
    new = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in new) == sorted(NEW)
    for m in new:
        assert CELL in m["workloads"] and m["moves"] == "out_tokens_per_s"
        mod = __import__(f"benchmark.layer_metrics.{m['name']}",
                         fromlist=["x"])
        assert (mod.UNIT, mod.LAYER, mod.SOURCE, mod.MOVES) == (
            m["unit"], m["layer"], m["source"], m["moves"])


def test_the_check_sessions_document_is_the_stated_length():
    from benchmark.traffic import closed_sessions

    reqs = serve_dp_solar.check_requests(
        closed_sessions, {**TRAFFIC, "kv_block_size": 16}, 2303000001)
    assert len(reqs) == 4 and all(r["max_tokens"] == 128 for r in reqs)
    doc = os.path.commonprefix([r["prompt"] for r in reqs])
    assert 8192 - 1 <= len(doc) + 1 <= 8192 + 8     # the questions' heads
    assert all(8192 + 64 <= r["prompt_tokens"] <= 8192 + 256 for r in reqs)
    # the window's documents keep their own lengths
    first = next(closed_sessions.stream(TRAFFIC, 2303000001, 0))
    assert 8192 + 64 <= first["prompt_tokens"] <= 16384 + 256


def test_the_byte_count_is_the_programs():
    """lib/bytes_solar.py counts parameters from the file's numbers alone;
    the program's own shapes give the same bytes, and a slot's state is a
    snapshot's."""
    import jax

    from ray_tpu.llm import _solar_steps
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import solar

    cfg = solar.SolarConfig.solar_open2(
        **serve_dp_solar.model_overrides(CONFIG))
    shapes = jax.eval_shape(
        lambda: solar.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))
    assert bytes_solar.weight_bytes(CONFIG)["held"] == held
    assert 6.6e9 < held < 6.7e9
    caches = jax.eval_shape(lambda: _solar_steps.alloc_cache(
        cfg, EngineConfig(**CONFIG["engine"])))
    kc, vc, state, tails, snap_state, snap_tails = caches
    assert kc.shape == (1, 32769, 16, 8, 128)
    assert state.shape == (3, 16, 64, 128, 128) and snap_state.shape == (
        65, 3, 64, 128, 128)
    one = (snap_state.size * 4 + snap_tails.size * 2) / 65
    assert bytes_solar.slot_bytes(CONFIG) == one
    resident = held + sum(c.size * c.dtype.itemsize for c in caches)
    assert 0.61 < resident / 15.75e9 < 0.64


COUNTS = {"steps": 100.0, "steps_with_chunk": 90.0,
          "prefill_chunk_tokens": 90 * 250.0,
          "moe_pairs_routed": (90 * 250 + 100 * 15) * 8 * 4.0,
          "moe_pairs_held": (90 * 250 + 100 * 15) * 4.0,
          "moe_experts_touched": 100 * 4 * 38.0,
          "kv_positions_live": 100 * 15 * 12000.0,
          "attn_positions_live": 100 * 15 * 12000.0,
          "chunk_positions_live": 90 * 8000.0,
          "chunk_attn_pairs": 90 * 250 * 7900.0,
          "snapshots_taken": 80.0, "snapshots_restored": 6.0}


def test_a_steps_bytes_and_operations_from_the_counters():
    need = bytes_solar.step_bytes(CONFIG, COUNTS)
    assert need["total"] == sum(v for k, v in need.items() if k != "total")
    per = {k: v / 100 for k, v in need.items()}
    assert 1.39e9 < per["weights"] < 1.41e9          # outside the experts
    assert per["experts"] == 4 * 38 * 3 * 4096 * 1280 * 2
    # 15 decoding slots and 0.9 chunks a step, state read and written
    assert per["state"] == pytest.approx(2 * 15.9 * 13025280)
    assert per["kv"] == pytest.approx((15 * 12000 + 0.9 * 8000) * 4096)
    assert per["snapshots"] == pytest.approx(0.86 * 13025280)
    did = bytes_solar.step_flops(CONFIG, COUNTS)
    assert did["total"] == sum(v for k, v in did.items() if k != "total")
    rows = 90 * 250 + 100 * 15
    assert did["experts"] == 2.0 * rows * 4 * 3 * 4096 * 1280
    assert did["head"] == 2.0 * (1500 + 90) * 4096 * 24576
    assert did["attention"] == 4 * 64 * 128 * (
        100 * 15 * 12000.0 + 90 * 250 * 7900.0)
    # a row's matmuls outside the experts: ~0.6 G parameters, twice
    assert 1.1e9 < did["matmuls"] / rows < 1.3e9
    assert bytes_solar.rows_of(CONFIG, COUNTS)["decode_rows"] == 1500


def art_with(counts, step_ms=15.0):
    zero = {k: 0 for k in counts}
    art = {"config": serve_dp_solar.reader_config(CONFIG),
           "engine": CONFIG["engine"], "device": {"kind": "TPU v5 lite"},
           "stats_open": {**zero, "prefix_cache": {"block_hits": 0},
                          "snapshot_rerun_tokens": 0},
           "stats_close": {**counts, "prefix_cache": {"block_hits": 3000},
                           "snapshot_rerun_tokens": 480}}
    return art


def test_the_roofline_and_the_peak_share_read_the_counters(monkeypatch):
    from benchmark.layer_metrics import decode_device_ms_per_step

    monkeypatch.setattr(decode_device_ms_per_step, "read", lambda art: 15.0)
    art = art_with(COUNTS)
    roof = solar_step_hbm_roofline.read(art)
    need = bytes_solar.step_bytes(CONFIG, COUNTS)["total"]
    assert roof == pytest.approx(100 * need / 819e9 / (100 * 15e-3))
    assert 40 < roof < 100
    mfu = solar_step_mfu.read(art)
    did = bytes_solar.step_flops(CONFIG, COUNTS)["total"]
    assert mfu == pytest.approx(100 * did / 197e12 / (100 * 15e-3))
    assert 5 < mfu < 40
    assert art["solar_step_bytes"]["total"] == pytest.approx(need / 100)
    assert snapshot_rerun_share.read(art) == pytest.approx(1.0)
    assert chunk_step_share.read(art) == pytest.approx(90.0)
    assert moe_held_pair_share.read(art) == pytest.approx(12.5)
    # the standing reader finds the held count under Ling's key
    art["stats_close"]["moe_load_max"] = 100 * 4 * 30
    art["stats_open"]["moe_load_max"] = 0
    assert moe_load_max_over_mean.read(art) == pytest.approx(
        100 * 4 * 30 * 40 / COUNTS["moe_pairs_held"])


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """A run that was not traced, or a program without the counters (any
    parent of this PR), leaves the metric out and does not raise."""
    for art in ({}, {"stats_open": {"steps": 1, "prefix_cache": None},
                     "stats_close": {"steps": 9, "prefix_cache": None},
                     "config": CONFIG, "engine": CONFIG["engine"],
                     "device": {"kind": "TPU v5 lite"}}):
        for reader in (gqa_device_share, snapshot_rerun_share,
                       chunk_step_share, solar_step_hbm_roofline,
                       solar_step_mfu):
            assert reader.read(dict(art)) is None


META = [  # (start, duration, tf_op) of one execution of the step
    (0.0, 30.0, "jit(paged_decode_step)/kda/mul:"),
    (30.0, 20.0, "jit(paged_decode_step)/gqa/dot_general:"),
    (50.0, 40.0, None),                                  # the grouped matmul
    (90.0, 10.0, "jit(paged_decode_step)/rsqrt:"),
    (100.0, 50.0, "jit(paged_decode_step)/gqa/while:"),   # the chunk's tiles
    (110.0, 15.0, "jit(paged_decode_step)/gqa/while/body/dot_general:"),
    (130.0, 10.0, "jit(paged_decode_step)/gqa/while/body/exp:"),
    (150.0, 25.0, "jit(paged_decode_step)/gqa/paged_decode_attention:"),
]


def test_gqa_and_the_standing_scopes_sum_to_busy():
    """`gqa_device_share` reads the trace's own `tf_op`; lib/scopes.py joins
    labels to the compiled text and knows `kda`, `mla`, `moe` alone, so `gqa`
    is inside its `rest`: kda + moe + gqa + (rest - gqa) = busy."""
    planes = {"/device:TPU:0": {"XLA Ops": [
        (s, d, {"tf_op": op, "name": f"%op.{i}"})
        for i, (s, d, op) in enumerate(META)]}}
    gqa = scopes_solar.scope_seconds(planes, "gqa", 0.0, 200.0)
    assert gqa == pytest.approx((20 + 50 + 25) * 1e-9)
    assert scopes_solar.scope_seconds(planes, "kda", 0.0, 200.0) == (
        pytest.approx(30e-9))
    assert scopes_solar.scope_seconds({}, "gqa", 0.0, 1.0) is None
    labels = ["%fusion.1 = f32[8]{0} fusion(%a)", "%fusion.2 = bf16[4]{0} fusion(%b)",
              '%ragged-dot-none.3 = bf16[512,8]{1,0} custom-call(%c), '
              'custom_call_target="tpu_custom_call"',
              "%fusion.7 = f32[8]{0} fusion(%a)", "%while.5 = (s32[]) while(%t)",
              "%fusion.8 = f32[2]{0} fusion(%a)", "%fusion.9 = f32[3]{0} fusion(%a)",
              '%paged.6 = bf16[16,64,128]{2,1,0} custom-call(%q), '
              'custom_call_target="tpu_custom_call"']
    trace = {"/device:TPU:0": {
        "XLA Modules": [("jit_paged_decode_step(1)", 0.0, 200.0)],
        "XLA Ops": [(name, s, d) for name, (s, d, _) in zip(labels, META)]}}
    found = {"fusion.1 f32[8]": "kda"}
    times = scopes.scope_times(trace, {"jit_paged_decode_step": found},
                               0.0, 200.0)
    busy = sum(times.values())
    assert busy == pytest.approx((30 + 20 + 40 + 10 + 50 + 25) * 1e-9)
    assert times["kda"] + times["moe"] + gqa + (times["rest"] - gqa) == (
        pytest.approx(busy))
    assert gqa <= times["rest"]


def test_the_runner_puts_every_seam_back(monkeypatch):
    before = (serve_dp.model_overrides, serve_dp.sum_stats,
              serve_dp.check_requests, serve_dp.judge_check,
              serve_dp.CHECK_TOLERANCE_BF16_STEPS,
              _inside.engine_reference_check)
    seen = {}

    def fake_run(ctx):
        seen["overrides"] = serve_dp.model_overrides(ctx.config)
        seen["check"] = _inside.engine_reference_check
        seen["requests"] = serve_dp.check_requests
        seen["sum"] = serve_dp.sum_stats([
            {"steps": 1, "tokens_out": 2, "mid_decode_admissions": 0,
             "blocks_in_use": 3, "prefix_cache": None,
             **{k: 5 for k in serve_dp_solar.COUNTERS}}] * 2)
        raise RuntimeError("the run failed")

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, True, "/nowhere"

    monkeypatch.setattr(serve_dp, "run", fake_run)
    with pytest.raises(RuntimeError):
        serve_dp_solar.run(Ctx)
    assert before == (serve_dp.model_overrides, serve_dp.sum_stats,
                      serve_dp.check_requests, serve_dp.judge_check,
                      serve_dp.CHECK_TOLERANCE_BF16_STEPS,
                      _inside.engine_reference_check)
    o = seen["overrides"]
    assert (o["n_held"], o["n_experts"], o["layer_ids"]) == (40, 320, (4, 5, 6, 7))
    assert (o["gqa_period"], o["gate_rank"], o["beta_scale"]) == (4, 128, 2.0)
    assert seen["check"].keywords["scopes_path"] == "/nowhere/scopes.json"
    assert seen["check"].keywords["state_steps"] == 256
    assert seen["requests"] is serve_dp_solar.check_requests
    assert seen["sum"]["snapshots_restored"] == 10 and seen["sum"]["steps"] == 2
    assert set(bytes_solar.COUNTERS) <= set(seen["sum"])


def test_a_program_without_the_family_fails_at_once(monkeypatch):
    import ray_tpu.llm

    monkeypatch.setattr(ray_tpu.llm, "MODEL_FAMILIES", {
        k: v for k, v in ray_tpu.llm.MODEL_FAMILIES.items() if k != "solar"})

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, False, "/nowhere"

    with pytest.raises(AssertionError, match="no model family 'solar'"):
        serve_dp_solar.run(Ctx)


SOUND = {"router_f32_steps": 0.1, "router_f32_steps_bf16": 900.0,
         "state_error": 2e-6, "state_error_bf16": 6e-3, "state_steps": 500,
         "chunk_rows": 240}


def test_judge_check_holds_the_replays_the_resumes_and_the_mechanisms():
    first = {"gaps": [0.0, 0.01], "max_abs_logit": 4.0, "argmax_equal": 1,
             "replay_equal": True, "resume_from": 0, "served_resumed": 3,
             "routing": {"expert_steps": 3.0, "same_experts": 0.9}}
    later = {**first, "resume_from": 8192, "mechanisms": SOUND}
    assert serve_dp_solar.judge_check([first, later, later], 8.0)["ok"]
    # no replay resumed, or no served request did: not correct
    out = serve_dp_solar.judge_check([first, {**later, "resume_from": 0}], 8.0)
    assert not out["ok"] and not out["resumed"]
    assert not serve_dp_solar.judge_check(
        [first, {**later, "served_resumed": 0}], 8.0)["ok"]
    assert not serve_dp_solar.judge_check([first], 8.0)["ok"]
    assert not serve_dp_solar.judge_check(
        [first, {**later, "replay_equal": False}], 8.0)["ok"]
    assert not serve_dp_solar.judge_check(
        [first, {**later, "gaps": [1.0]}], 8.0)["ok"]
    far = {**later, "routing": {"expert_steps": 41.0, "same_experts": 0.5}}
    assert not serve_dp_solar.judge_check([first, far], 8.0)["ok"]
    for key, over in (("router_f32_steps", 33.0), ("state_error", 2e-3)):
        out = serve_dp_solar.judge_check(
            [first, {**later, "mechanisms": {**SOUND, key: over}}], 8.0)
        assert not out["ok"] and out[key] == over
        assert out[f"{key}_bf16"] == SOUND[f"{key}_bf16"]
    # nobody's mechanisms were read: not correct
    assert not serve_dp_solar.judge_check(
        [first, {k: v for k, v in later.items() if k != "mechanisms"}],
        8.0)["ok"]


# --- planted faults come out not correct ----------------------------------------

TINY = load("configs", "tiny-solar.json")


def tiny_engine(seed=7):
    import jax

    from ray_tpu.llm._engine import EngineConfig, PagedEngine
    from ray_tpu.models import solar

    cfg = solar.SolarConfig.tiny(**serve_dp_solar.model_overrides(TINY))
    params = solar.seeded_params(cfg, jax.random.PRNGKey(seed))
    return PagedEngine(cfg, params, EngineConfig(**TINY["engine"]))


def tiny_session():
    rng = np.random.default_rng(3)
    doc = [256] + [int(t) for t in rng.integers(0, 256, 299)]
    return [doc + [int(t) for t in rng.integers(0, 256, n)]
            for n in (30, 41, 52)]


def dropped_hand_over(monkeypatch):
    """A chunk starts from zeros, not from what the chunk before left."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    real = kda.kda_chunked
    monkeypatch.setattr(kda, "kda_chunked", lambda q, k, v, g, beta, s, **kw:
                        real(q, k, v, g, beta, jnp.zeros_like(s), **kw))


def stale_snapshot(monkeypatch):
    """A request resumes at the deepest snapshot's position from the state
    of the snapshot before it: one chunk stale."""
    from ray_tpu.llm._prefix_cache import PrefixCache

    real = PrefixCache.deepest_snapshot

    def stale(self, keys, n_blocks):
        covered, entry = real(self, keys, n_blocks)
        _, before = real(self, keys, covered - 1)
        return covered, (before if before >= 0 else entry)

    monkeypatch.setattr(PrefixCache, "deepest_snapshot", stale)


def low_state(monkeypatch):
    """The program carrying its recurrent state in bf16, in the chunks and in
    the decode rows."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    step, chunked = kda.kda_step, kda.kda_chunked

    def low(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def step_low(q, k, v, g, beta, state):
        o, new = step(q, k, v, g, beta, state)
        return o, low(new)

    def chunked_low(q, k, v, g, beta, state, **kw):
        o, new = chunked(q, k, v, g, beta, state, **kw)
        return o, low(new)

    monkeypatch.setattr(kda, "kda_step", step_low)
    monkeypatch.setattr(kda, "kda_chunked", chunked_low)


def low_router(monkeypatch):
    """The program's router with weights and logits in bf16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ling

    def scores(cfg, p, x):
        z = jnp.dot(x.astype(jnp.bfloat16), p["router"].astype(jnp.bfloat16),
                    preferred_element_type=jnp.bfloat16)
        return jax.nn.sigmoid(z.astype(jnp.float32))

    monkeypatch.setattr(ling, "router_scores", scores)


@pytest.mark.parametrize("plant,fails_by", [
    (None, set()), (dropped_hand_over, {"worst_gap_bf16_steps"}),
    (stale_snapshot, {"worst_gap_bf16_steps"}),
    (low_state, {"state_error"}), (low_router, {"router_f32_steps"})])
def test_a_planted_fault_in_the_program_is_not_correct(monkeypatch, plant,
                                                       fails_by):
    """The check as the cell runs it (`_inside_solar.engine_reference_check`
    on an engine that served the session, then `judge_check`), at the
    rehearsal's size: the program as it is passes; a dropped hand-over and a
    stale snapshot show in the logits against the reference from position
    0, a bf16 state and a bf16 router by their own limits."""
    if plant:
        plant(monkeypatch)
    engine = tiny_engine()

    async def check():
        samples = []
        for p in tiny_session():
            toks = [t async for t in engine.generate_stream(p, max_tokens=6)]
            samples.append({"prompt_ids": p, "answer_ids": toks})
        return await _inside_solar.engine_reference_check(
            types.SimpleNamespace(engine=engine), None, samples, 64,
            config=serve_dp_solar.reference_hp(TINY), state_steps=24,
            second_readings=True)

    out = serve_dp_solar.judge_check(
        asyncio.run(check()), serve_dp_solar.CHECK_TOLERANCE_BF16_STEPS)
    limits = {**serve_dp_solar.ROUTER_TOLERANCE_STEPS,
              **serve_dp_solar.MECHANISM_LIMITS,
              "worst_gap_bf16_steps": out["tolerance_steps"]}
    over = {k for k, limit in limits.items() if out[k] > limit}
    assert out["ok"] is (plant is None), out
    assert out["resumed"] and out["served_resumed"] >= 2
    assert out["resumed_from"][0] == 0 and min(out["resumed_from"][1:]) >= 256
    if plant in (dropped_hand_over, stale_snapshot):
        # a wrong state moves the routing too
        assert fails_by <= over
    else:
        assert over == fails_by
        assert out["replays_equal"]
    # the second readings, logged by every run, are over their limits
    assert out["router_f32_steps_bf16"] > 16 * limits["router_f32_steps"]
    assert out["state_error_bf16"] > 4 * limits["state_error"]
    assert out["fp8_activations_gap_steps"] > 2 * out["tolerance_steps"]
    assert out["state_steps"] >= 24 and out["state_chunk_rows"] > 0


@pytest.mark.parametrize("trace,seed", [(0, 5), (1, 2 ** 31 + 11)])
def test_the_tiny_solar_cell_runs_end_to_end_on_the_cpu(trace, seed):
    proc = run_cell("tiny-longdocqa-closed", trace, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {}
    log = proc.stderr
    assert "'replays_equal': True" in log and "'resumed': True" in log
    assert "'resumed_from': [0, 256, 256]" in log
    assert "'expert_steps': 0.0" in log and "'router_f32_steps': 0.0" in log
    summary = json.loads(next(
        ln for ln in log.splitlines() if "summary: " in ln
    ).split("summary: ", 1)[1])
    close = summary["stats_close"]
    assert close["snapshots_restored"] > 2 and close["steps_with_chunk"] > 0
    assert close["prefix_cache"]["block_hits"] > 0
    if trace:
        with open(os.path.join(ROOT, ".bench_out", "tiny-longdocqa-closed",
                               "scopes.json")) as f:
            found = json.load(f)
        assert set(found) == {"jit_paged_decode_step", "jit_paged_prefill"}
        assert {"kda", "moe"} == set(found["jit_paged_decode_step"].values())
        assert "rehearsal metrics" in log and "chunk_step_share" in log

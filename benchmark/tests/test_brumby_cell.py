"""The Brumby cell's own pieces on the CPU: the configuration file against the
catalog's numbers, the traffic generator, the runner's seams, the byte,
operation and counter readers, planted faults that the check must refuse, and
the rehearsal twin end to end.

    python -m pytest benchmark/tests/test_brumby_cell.py -q        (not part of tier-1)
"""

import asyncio
import collections
import json
import os
import types

import numpy as np
import pytest

from benchmark.layer_metrics import (brumby_step_hbm_roofline, brumby_step_mfu,
                                     chunk_step_share, retention_device_share,
                                     retention_step_hbm_roofline,
                                     snapshot_rerun_share,
                                     snapshot_shared_share)
from benchmark.lib import bytes_brumby
from benchmark.runners import _inside, _inside_brumby, serve_dp, serve_dp_brumby
from benchmark.tests.test_rehearsal import RESULT_KEYS, ROOT, load, run_cell
from benchmark.traffic import closed_tasks

CONFIG = load("configs", "brumby-14b-l6.json")
TRAFFIC = load("traffic", "manyshot-closed.json")
CELL = "brumby14b-manyshot-closed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["retention_device_share", "retention_step_hbm_roofline",
       "brumby_step_hbm_roofline", "brumby_step_mfu", "snapshot_shared_share"]


def test_the_configuration_keeps_every_published_number_but_the_depth():
    assert set(CONFIG["reduced"]) == {"num_hidden_layers"}
    cut = CONFIG["reduced"]["num_hidden_layers"]
    assert (cut["published"], cut["here"]) == (40, 6)
    assert CONFIG["num_hidden_layers"] == 6 >= 4
    kept = {"hidden_size": 5120, "num_attention_heads": 40, "head_dim": 128,
            "num_key_value_heads": 8, "intermediate_size": 17408,
            "vocab_size": 151936, "rope_theta": 1000000,
            "rms_norm_eps": 1e-06, "max_position_embeddings": 32768,
            "max_window_layers": 40}
    assert {k: CONFIG[k] for k in kept} == kept
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Brumby-14B-Base")
        assert CONFIG["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
        assert differ == {"num_hidden_layers"}
    assumed = " ".join(CONFIG["assumed"])
    for test in ("test_assumed_power_two_phi_inner_product_is_the_squared_score",
                 "test_assumed_one_gate_and_one_state_a_kv_head_shared_by_its_group",
                 "test_assumed_gate_bias_zero_is_the_bias_free_gate_and_seeded_heads_remember",
                 "test_assumed_scale_inside_the_power",
                 "test_assumed_eps_on_the_normaliser",
                 "test_assumed_qk_norm_and_rotary_kept",
                 "test_assumed_float32_state_a_bf16_state_fails"):
        assert test in assumed
        with open(os.path.join(ROOT, "tests", "test_brumby.py")) as f:
            assert f"def {test}(" in f.read()


def test_the_cell_and_its_traffic_are_the_issues_to_the_number():
    cell = load("workloads", f"{CELL}.json")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-l6", "manyshot-closed", 1)
    assert cell["end_to_end"] == ["out_tokens_per_s", "setup_s"]
    t = TRAFFIC
    assert (t["generator"], t["clients"], t["lanes"], t["pool"],
            t["items_per_task"], t["ramp_s"]) == ("closed_tasks", 16, 4, 32,
                                                  64, 30)
    assert t["header_tokens"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert t["item_tokens"] == {"dist": "uniform", "min": 64, "max": 512}
    assert t["answer_tokens"] == {"dist": "uniform", "min": 128, "max": 384}
    assert t["check"]["header_tokens"] == 8192 and t["check"]["requests"] == 4
    assert t["check"]["state_steps"] == 256
    e = CONFIG["engine"]
    assert e == {"max_num_seqs": 16, "kv_block_size": 16,
                 "num_kv_blocks": 65536, "max_model_len": 32768,
                 "prefix_cache": True, "num_state_snapshots": 8}
    assert e["max_model_len"] == CONFIG["max_position_embeddings"]
    # names alone: twice what 16 requests at the longest could name
    assert e["num_kv_blocks"] == 2 * 16 * e["max_model_len"] // 16
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, not by place: a later PR appends behind these
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["why"] == cell["why"] and entry["chips"] == 1
    config = next(c for c in bench["configs"] if c["name"] == "brumby-14b-l6")
    assert config["file"].endswith("brumby-14b-l6.json")
    assert config["reduced"] == ["num_hidden_layers"]
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"]) and len(listed) == 16 + 5
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


# --- the generator ---------------------------------------------------------------


def take(params, seed, client, n):
    s = closed_tasks.stream(params, seed, client)
    return [next(s) for _ in range(n)]


def test_closed_tasks_is_a_pure_function_of_its_arguments():
    a = take(TRAFFIC, 2303000017, 5, 20)
    assert a == take(TRAFFIC, 2303000017, 5, 20)
    assert a != take(TRAFFIC, 2303000018, 5, 20)
    assert a != take(TRAFFIC, 2303000017, 6, 20)


def test_lanes_offsets_and_round_robin_are_as_stated():
    seed = 2 ** 31 + 7
    got = {c: take(TRAFFIC, seed, c, 40) for c in range(16)}
    for c, reqs in got.items():
        lane, rank = c % 4, c // 4
        assert {r["lane"] for r in reqs} == {lane}
        # lane l starts 16 l items into its first task; a caller takes every
        # fourth item; 64 items a task
        at = [r["task"] * 64 + r["item"] for r in reqs]
        assert at == [16 * lane + rank + 4 * m for m in range(40)]
        assert all(4096 + 64 <= r["prompt_tokens"] <= 8192 + 512 for r in reqs)
        assert all(128 <= r["max_tokens"] <= 384 for r in reqs)
    # one header a (lane, task), shared by the lane's callers and by no
    # other lane
    headers = collections.defaultdict(set)
    for c, reqs in got.items():
        for r in reqs:
            headers[(r["lane"], r["task"])].add(r["prompt"][:4000])
    assert all(len(h) == 1 for h in headers.values())
    assert len({next(iter(h)) for h in headers.values()}) == len(headers)
    # the lane's four callers together take each item of a task once
    lane0 = sorted((r["task"], r["item"]) for c in (0, 4, 8, 12)
                   for r in got[c] if r["task"] == 0)
    assert lane0 == [(0, i) for i in range(64)]
    # an item's prompt parts from its header at the item's head
    a, b = got[0][0], got[4][0]
    common = os.path.commonprefix([a["prompt"], b["prompt"]])
    assert common.endswith(" I") and len(common) + 1 >= 4096 - 1


def test_every_seed_offers_the_same_multiset_of_lengths():
    def lengths(seed):
        tasks, items = {}, []
        for lane in range(4):
            # a lane's first 8 tasks are 8 of the pool's 32, disjoint
            s = closed_tasks.stream({**TRAFFIC, "clients": 4}, seed, lane)
            for _ in range(8 * 64 - 16 * lane):
                r = next(s)
                head = len(r["prompt"].split(" I%x: " % r["item"])[0]) + 1
                tasks[(r["lane"], r["task"])] = head
                items.append((r["prompt_tokens"] - head, r["max_tokens"]))
        return sorted(tasks.values()), sorted(items)

    a, b = lengths(3), lengths(2 ** 31 + 5)
    assert a[0] == b[0] and len(a[0]) == 32
    assert a[0][0] >= 4096 and a[0][-1] <= 8192
    # lanes that start into their first task leave different items out, so
    # the whole pool is compared through a lane that starts at its head
    def pool(seed):
        s = closed_tasks.stream({**TRAFFIC, "clients": 1, "lanes": 1}, seed, 0)
        return sorted((lambda r: r["max_tokens"])(next(s))
                      for _ in range(32 * 64))
    assert pool(3) == pool(2 ** 31 + 5)
    assert sum(pool(3)) / len(pool(3)) == pytest.approx(256, abs=1)


def test_the_check_task_is_its_own_with_the_stated_header():
    reqs = serve_dp_brumby.check_requests(
        closed_tasks, {**TRAFFIC, "kv_block_size": 16}, 2303000001)
    assert len(reqs) == 4 and all(r["max_tokens"] == 128 for r in reqs)
    assert {r["lane"] for r in reqs} == {16} and {r["task"] for r in reqs} == {0}
    header = os.path.commonprefix([r["prompt"] for r in reqs])
    assert 8192 - 1 <= len(header) + 1 <= 8192 + 4      # the items' heads
    assert all(8192 + 64 <= r["prompt_tokens"] <= 8192 + 512 for r in reqs)
    # the window's headers keep their own lengths
    first = next(closed_tasks.stream(TRAFFIC, 2303000001, 0))
    assert 4096 + 64 <= first["prompt_tokens"] <= 8192 + 512
    assert not first["prompt"].startswith(header[:40])


# --- bytes, operations, readers -------------------------------------------------


def test_the_byte_and_operation_counts_against_hand_counts():
    w = bytes_brumby.weight_bytes(CONFIG)
    layer = (5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 5120 * 5120
             + 3 * 5120 * 17408)
    assert bytes_brumby.layer_matmul_params(CONFIG) == layer == 330_342_400
    assert w["layer"] == (layer + 2 * 5120 + 2 * 128) * 2 + 8 * 4
    assert w["step"] == 6 * w["layer"] + (5120 + 5120 * 151936) * 2
    assert w["held"] == pytest.approx(7.075e9, rel=1e-3)
    assert w["step"] == pytest.approx(5.52e9, rel=2e-3)
    # the symmetric square of a 128-channel key, whatever the program holds
    assert bytes_brumby.phi_rows(CONFIG) == 8256
    slot = 6 * 8 * (8256 * 128 + 8256) * 4
    assert bytes_brumby.slot_bytes(CONFIG) == slot == pytest.approx(204.5e6,
                                                                    rel=1e-3)
    assert bytes_brumby.retention_step_bytes(CONFIG, 16) == 2 * 16 * slot
    d = {"steps": 100.0, "steps_with_chunk": 12.0,
         "prefill_chunk_tokens": 2000.0, "rows_decoded": 1600.0,
         "snapshots_taken": 1.0, "snapshots_restored": 6.0}
    need = bytes_brumby.step_bytes(CONFIG, d)
    assert need["weights"] == 100 * w["step"] + 3600 * 5120 * 2
    assert need["state"] == 2 * 1612 * slot
    assert need["snapshots"] == 7 * slot
    assert need["total"] == sum(v for k, v in need.items() if k != "total")
    did = bytes_brumby.step_flops(CONFIG, d)
    assert did["matmuls"] == 2 * 3600 * 6 * layer
    assert did["head"] == 2 * 1612 * 5120 * 151936
    assert did["retention"] == 2 * 3600 * 6 * (40 + 8) * 8256 * 128
    assert did["total"] == sum(v for k, v in did.items() if k != "total")


def art_with(d, **extra):
    zero = {k: 0 for k in serve_dp_brumby.COUNTERS}
    return {"config": CONFIG, "engine": CONFIG["engine"],
            "device": {"kind": "TPU v5 lite"},
            "stats_open": {**zero, "steps": 0,
                           "prefix_cache": {"block_hits": 0}},
            "stats_close": {**zero, **d,
                            "prefix_cache": {"block_hits": 40000}}, **extra}


COUNTS = {"steps": 100, "steps_with_chunk": 12, "prefill_chunk_tokens": 2000,
          "rows_decoded": 1600, "snapshots_taken": 1, "snapshots_restored": 6,
          "snapshots_shared": 6, "snapshot_rerun_tokens": 6400}


def test_the_rooflines_and_the_peak_share_read_the_counters(monkeypatch):
    from benchmark.layer_metrics import decode_device_ms_per_step

    monkeypatch.setattr(decode_device_ms_per_step, "read", lambda art: 21.0)
    art = art_with(COUNTS)
    d = {k: float(COUNTS[k]) for k in bytes_brumby.COUNTERS}
    roof = brumby_step_hbm_roofline.read(art)
    need = bytes_brumby.step_bytes(CONFIG, d)["total"]
    assert roof == pytest.approx(100 * need / 819e9 / (100 * 21e-3))
    assert 40 < roof < 100
    mfu = brumby_step_mfu.read(art)
    did = bytes_brumby.step_flops(CONFIG, d)["total"]
    assert mfu == pytest.approx(100 * did / 197e12 / (100 * 21e-3))
    assert 1 < mfu < 40
    assert art["brumby_step_bytes"]["total"] == pytest.approx(need / 100)
    assert snapshot_shared_share.read(art) == pytest.approx(100.0)
    assert snapshot_rerun_share.read(art) == pytest.approx(1.0)
    assert chunk_step_share.read(art) == pytest.approx(12.0)
    # the kernel: 6 calls a step in the trace, each 1.9 ms, 16 rows a step
    calls = [("%retention_step.5 = (f32[16,8,8,128]) custom-call(...), "
              'custom_call_target="tpu_custom_call"', 0.0, 1.9e6)] * 12
    calls.append(("%paged_decode_attention.1 = custom-call()", 0.0, 5e6))
    art = art_with(COUNTS, trace={"pallas_events": calls})
    got = retention_step_hbm_roofline.read(art)
    per_call = 2 * 16 * bytes_brumby.slot_bytes(CONFIG) / 6
    assert got == pytest.approx(100 * per_call / 819e9 / 1.9e-3)
    assert 50 < got < 100
    assert art["retention_step_ms_per_call"] == pytest.approx(1.9)


def test_the_new_readers_return_nothing_where_there_is_nothing():
    """A run that was not traced, or a program without the counters (any
    parent of this PR), leaves the metric out and does not raise."""
    for art in ({}, {"stats_open": {"steps": 1, "prefix_cache": None},
                     "stats_close": {"steps": 9, "prefix_cache": None},
                     "config": CONFIG, "engine": CONFIG["engine"],
                     "device": {"kind": "TPU v5 lite"},
                     "trace": {"pallas_events": []}}):
        for reader in (retention_device_share, retention_step_hbm_roofline,
                       brumby_step_hbm_roofline, brumby_step_mfu,
                       snapshot_shared_share):
            assert reader.read(dict(art)) is None


# --- the runner's seams ----------------------------------------------------------


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
             **{k: 5 for k in serve_dp_brumby.COUNTERS}}] * 2)
        raise RuntimeError("the run failed")

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, True, "/nowhere"

    monkeypatch.setattr(serve_dp, "run", fake_run)
    with pytest.raises(RuntimeError):
        serve_dp_brumby.run(Ctx)
    assert before == (serve_dp.model_overrides, serve_dp.sum_stats,
                      serve_dp.check_requests, serve_dp.judge_check,
                      serve_dp.CHECK_TOLERANCE_BF16_STEPS,
                      _inside.engine_reference_check)
    o = seen["overrides"]
    assert (o["dim"], o["n_layers"], o["n_heads"], o["n_kv_heads"],
            o["head_dim"], o["ffn_dim"], o["vocab_size"]) == (
        5120, 6, 40, 8, 128, 17408, 151936)
    assert (o["rope_theta"], o["norm_eps"], o["ret_eps"]) == (
        1000000, 1e-6, 1e-6)
    assert seen["check"].keywords["scopes_path"] == "/nowhere/scopes.json"
    assert seen["check"].keywords["state_steps"] == 256
    assert seen["requests"] is serve_dp_brumby.check_requests
    assert seen["sum"]["snapshots_shared"] == 10 and seen["sum"]["steps"] == 2
    assert set(bytes_brumby.COUNTERS) <= set(seen["sum"])


def test_a_program_without_the_family_fails_at_once(monkeypatch):
    import ray_tpu.llm

    monkeypatch.setattr(ray_tpu.llm, "MODEL_FAMILIES", {
        k: v for k, v in ray_tpu.llm.MODEL_FAMILIES.items() if k != "brumby"})

    class Ctx:
        config, traffic, trace, out_dir = CONFIG, TRAFFIC, False, "/nowhere"

    with pytest.raises(AssertionError, match="no model family 'brumby'"):
        serve_dp_brumby.run(Ctx)


SOUND = {"state_error": 2e-6, "state_error_bf16": 6e-3, "gamma_error": 1e-7,
         "gamma_error_bf16": 3e-2, "state_steps": 383, "state_rows": 8800}


def test_judge_check_holds_the_replays_the_resumes_and_the_mechanisms():
    first = {"gaps": [0.0, 0.01], "max_abs_logit": 4.0, "argmax_equal": 1,
             "replay_equal": True, "resume_from": 0, "served_shared": 2}
    later = {**first, "resume_from": 8192}
    probed = {**later, "mechanisms": SOUND}
    sound = [first, later, probed, later]
    assert serve_dp_brumby.judge_check(sound, 8.0)["ok"]
    # no later replay resumed, or no served request was answered from a
    # snapshot another left: not correct
    out = serve_dp_brumby.judge_check(
        [first, later, {**probed, "resume_from": 0}], 8.0)
    assert not out["ok"] and not out["resumed"]
    assert not serve_dp_brumby.judge_check(
        [{**g, "served_shared": 0} for g in sound], 8.0)["ok"]
    assert not serve_dp_brumby.judge_check([first, later], 8.0)["ok"]
    # the cold replay is held to the served cold run; a resumed replay that
    # parts from its served answer is reported
    assert not serve_dp_brumby.judge_check(
        [{**first, "replay_equal": False}, later, probed], 8.0)["ok"]
    out = serve_dp_brumby.judge_check(
        [first, later, {**probed, "replay_equal": False,
                        "replay_parts_at": 19}], 8.0)
    assert out["ok"] and out["replays_part_at"] == [-1, -1, 19]
    assert not serve_dp_brumby.judge_check(
        [first, later, {**probed, "gaps": [1.0]}], 8.0)["ok"]
    for key, over in (("state_error", 2e-3), ("gamma_error", 2e-3)):
        out = serve_dp_brumby.judge_check(
            [first, later, {**probed, "mechanisms": {**SOUND, key: over}}], 8.0)
        assert not out["ok"] and out[key] == over
        assert out[f"{key}_bf16"] == SOUND[f"{key}_bf16"]
    # nobody's mechanisms were read: not correct
    assert not serve_dp_brumby.judge_check([first, later, later], 8.0)["ok"]


# --- planted faults come out not correct ----------------------------------------

TINY = load("configs", "tiny-brumby.json")


def tiny_engine(seed=7):
    import jax

    from ray_tpu.llm._engine import EngineConfig, PagedEngine
    from ray_tpu.models import brumby

    cfg = brumby.BrumbyConfig.tiny(**serve_dp_brumby.model_overrides(TINY))
    params = brumby.init_params(cfg, jax.random.PRNGKey(seed))
    return PagedEngine(cfg, params, EngineConfig(**TINY["engine"]))


def tiny_task():
    rng = np.random.default_rng(3)
    header = [256] + [int(t) for t in rng.integers(0, 256, 299)]
    return [header + [int(t) for t in rng.integers(0, 256, n)]
            for n in (30, 41, 52, 36)]


def dropped_hand_over(monkeypatch):
    """A chunk starts from zeros, not from what the chunk before left."""
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as pr

    real = pr.retention_chunked
    monkeypatch.setattr(
        pr, "retention_chunked", lambda q, k, v, g, S, Z, eps: real(
            q, k, v, g, jnp.zeros_like(S), jnp.zeros_like(Z), eps))


def stale_snapshot(monkeypatch):
    """A snapshot is taken one chunk early: it says it holds the state at
    the match's end and holds the state of the chunk before."""
    from ray_tpu.llm._engine import PagedEngine

    real = PagedEngine._chunk_at

    def early(self, req, at, n):
        if (req is not None and req.take_at and at + n < req.take_at
                and at + n + self._ladder[-1] >= req.take_at
                and not getattr(req, "_early", False)):
            # reserve the entry now and attach it to the match's end later:
            # the step copies the slot's state a chunk too soon
            req._early = True
            was, req.take_at = req.take_at, at + n
            try:
                chunk_at, take = real(self, req, at, n)
            finally:
                req.take_at = was
            req._stale = take
            return chunk_at, -1
        chunk_at, take = real(self, req, at, n)
        stale = getattr(req, "_stale", -1)
        if take >= 0 and stale >= 0:
            self._prefix_cache._free_snaps.append(take)
            chunk_at[5] = self.ecfg.num_state_snapshots
            req._stale = -1
            return chunk_at, stale
        return chunk_at, take

    monkeypatch.setattr(PagedEngine, "_chunk_at", early)


def low_state(monkeypatch):
    """The program carrying its state and normaliser in bf16, in the chunks
    and in the decode rows."""
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as pr

    step, chunked = pr.retention_step_xla, pr.retention_chunked

    def low(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def step_low(*a):
        o, S, Z = step(*a)
        return o, low(S), low(Z)

    def chunked_low(*a):
        o, S, Z = chunked(*a)
        return o, low(S), low(Z)

    monkeypatch.setattr(pr, "retention_step_xla", step_low)
    monkeypatch.setattr(pr, "retention_chunked", chunked_low)


def dropped_normaliser(monkeypatch):
    """The decode rows divide by eps alone... and the chunks' by 1: the sum
    of the weights is left out."""
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as pr

    step = pr.retention_step_xla

    def unnormalised(q, k, v, gate, S, Z, eps):
        o, S, Z = step(q, k, v, gate, S, Z, eps)
        den = jnp.einsum("bkga,bkac,bkgc->bkg", q, Z, q)
        return o * (den[..., None] + eps), S, Z

    monkeypatch.setattr(pr, "retention_step_xla", unnormalised)


def low_gate(monkeypatch):
    """The program's gate with its logit in bf16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import brumby

    real = brumby.ret_inputs

    def inputs(cfg, p, x, positions):
        q, k, v, _, _ = real(cfg, p, x, positions)
        z = (x @ p["wg"]).astype(jnp.bfloat16) + p["bg"].astype(jnp.bfloat16)
        gamma = jax.nn.log_sigmoid(z).astype(jnp.float32)
        return q, k, v, gamma, jnp.exp(gamma)

    monkeypatch.setattr(brumby, "ret_inputs", inputs)


@pytest.mark.parametrize("plant,fails_by", [
    (None, set()), (dropped_hand_over, {"worst_gap_bf16_steps"}),
    (stale_snapshot, {"worst_gap_bf16_steps"}),
    (dropped_normaliser, {"worst_gap_bf16_steps"}),
    (low_state, {"state_error"}), (low_gate, {"gamma_error"})])
def test_a_planted_fault_in_the_program_is_not_correct(monkeypatch, plant,
                                                       fails_by):
    """The check as the cell runs it (`_inside_brumby.engine_reference_check`
    on an engine that served the task, then `judge_check`), at the
    rehearsal's size: the program as it is passes; a dropped hand-over, a
    stale snapshot and a dropped normaliser show in the logits against the
    reference from position 0, a bf16 state and a bf16 gate by their own
    limits."""
    if plant:
        plant(monkeypatch)
    engine = tiny_engine()

    async def check():
        samples = []
        for p in tiny_task():
            toks = [t async for t in engine.generate_stream(p, max_tokens=6)]
            samples.append({"prompt_ids": p, "answer_ids": toks})
        return await _inside_brumby.engine_reference_check(
            types.SimpleNamespace(engine=engine), None, samples, 128,
            config=TINY, state_steps=24, second_readings=True)

    out = serve_dp_brumby.judge_check(
        asyncio.run(check()), serve_dp_brumby.CHECK_TOLERANCE_BF16_STEPS)
    limits = {**serve_dp_brumby.MECHANISM_LIMITS,
              "worst_gap_bf16_steps": out["tolerance_steps"]}
    over = {k for k, limit in limits.items() if out[k] > limit}
    assert out["ok"] is (plant is None), out
    assert out["resumed"] and out["served_shared"] >= 2
    assert out["resumed_from"] == [0, 288, 288, 288]
    if plant in (dropped_hand_over, stale_snapshot, dropped_normaliser):
        assert fails_by <= over
    else:
        assert over == fails_by
        assert out["replays_equal"]
    # the second readings, logged by every run, are over their limits
    assert out["state_error_bf16"] > 4 * limits["state_error"]
    assert out["gamma_error_bf16"] > 4 * limits["gamma_error"]
    assert out["state_steps"] >= 24 and out["state_rows"] > 300


@pytest.mark.parametrize("trace,seed", [(0, 5), (1, 2 ** 31 + 11)])
def test_the_tiny_brumby_cell_runs_end_to_end_on_the_cpu(trace, seed):
    proc = run_cell("tiny-manyshot-closed", trace, seed=seed)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True, proc.stderr[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["metrics"] == {}
    log = proc.stderr
    assert "'replays_equal': True" in log and "'resumed': True" in log
    assert "'resumed_from': [0, 320, 320, 320]" in log
    summary = json.loads(next(
        ln for ln in log.splitlines() if "summary: " in ln
    ).split("summary: ", 1)[1])
    close = summary["stats_close"]
    assert close["snapshots_shared"] > 2 and close["steps_with_chunk"] > 0
    assert close["snapshots_shared"] == close["snapshots_restored"]
    assert close["kv_positions_live"] == 0 and close["rows_decoded"] > 0
    assert close["prefix_cache"]["block_hits"] > 0
    if trace:
        with open(os.path.join(ROOT, ".bench_out", "tiny-manyshot-closed",
                               "scopes.json")) as f:
            found = json.load(f)
        assert set(found) == {"jit_paged_decode_step", "jit_paged_prefill"}
        assert "rehearsal metrics" in log and "snapshot_shared_share" in log

"""The Ling family (models/ling.py, ops/kda.py, llm/_ling_steps.py and the
engine's per-slot state and latent pool) against the plain float32 reference
(benchmark/lib/reference_ling.py), at a tiny size on the CPU: hidden 64,
4 heads of 16, 16 experts in 4 groups (top-2 of 2 groups), 4 layers =
dense-KDA, KDA, KDA, MLA. One engine serves the engine cases.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ling as ref
from benchmark.runners._inside_ling import ProgramWeightsLing
from ray_tpu.llm._engine import EngineConfig, PagedEngine, chunk_ladder
from ray_tpu.models import ling
from ray_tpu.ops import grouped_ffn
from ray_tpu.ops import kda as kda_ops

HP = dict(hidden_size=64, num_attention_heads=4, head_dim=16,
          short_conv_kernel_size=4, kda_lower_bound=-5, kv_lora_rank=32,
          qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
          rope_theta=1e4, rms_norm_eps=1e-6, num_experts=16, n_group=4,
          topk_group=2, num_experts_per_tok=2, routed_scaling_factor=2.5,
          num_hidden_layers=4, first_k_dense_replace=1, layer_group_size=4,
          vocab_size=512)
CFG = ling.LingConfig.tiny()
ECFG = EngineConfig(max_num_seqs=4, kv_block_size=16, num_kv_blocks=64,
                    max_model_len=256)


@pytest.fixture(scope="module")
def params():
    return ling.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return ProgramWeightsLing(params)


@pytest.fixture(scope="module")
def engine(params):
    return PagedEngine(CFG, params, ECFG)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


def serve(engine, prompts, max_tokens=32):
    """The prompts through the engine's loop, all in flight at once."""
    async def go():
        return await asyncio.gather(*[
            engine.check_routing(p, max_tokens) for p in prompts])

    async def fresh_loop():
        # the engine's loop task belongs to one event loop
        engine._pending = engine._loop_task = None
        return await go()

    return asyncio.run(fresh_loop())


def kda_inputs(seed, T, H=4, dk=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = ref.l2_norm(jax.random.normal(ks[0], (T, H, dk))) * dk ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (T, H, dk)))
    v = jax.random.normal(ks[2], (T, H, dk))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, dk)) * 2 - 2)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    return q, k, v, g, beta


def test_forward_equals_the_reference(params, weights):
    toks = prompt(1, 200)                  # crosses three chunk edges
    padded = np.zeros(256, np.int32)
    padded[:200] = toks
    got = jax.jit(lambda p, t: ling.forward(CFG, p, t, 200))(
        params, jnp.asarray(padded))
    want = ref.logits_at(HP, weights, toks, list(range(200)))
    np.testing.assert_allclose(np.asarray(got)[:200], want, atol=1e-4)


@pytest.mark.parametrize("T", [1, 63, 64, 65, 200])
def test_chunked_kda_equals_the_recurrence(T):
    q, k, v, g, beta = kda_inputs(T, T)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (4, 16, 16))
    o, s = jax.jit(kda_ops.kda_chunked)(q, k, v, g, beta, s0)
    o_ref, s_ref = ref.kda_recurrence(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5)


def test_kda_survives_the_fastest_decay():
    """g = -5 on every channel and position: a factorised k / Gamma would
    overflow float32 inside one chunk."""
    q, k, v, g, beta = kda_inputs(3, 130)
    o, s = kda_ops.kda_chunked(q, k, v, jnp.full_like(g, -5.0), beta,
                               jnp.zeros((4, 16, 16)))
    o_ref, s_ref = ref.kda_recurrence(q, k, v, jnp.full_like(g, -5.0), beta)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, o_ref, atol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5)


def test_kda_decode_step_continues_a_prefill():
    q, k, v, g, beta = kda_inputs(4, 80)
    _, s = kda_ops.kda_chunked(q[:70], k[:70], v[:70], g[:70], beta[:70],
                               jnp.zeros((4, 16, 16)))
    outs = []
    for t in range(70, 80):
        o, s = kda_ops.kda_step(q[t][None], k[t][None], v[t][None],
                                g[t][None], beta[t][None], s[None])
        s = s[0]
        outs.append(o[0])
    o_ref, s_ref = ref.kda_recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(jnp.stack(outs), o_ref[70:], atol=1e-5)
    np.testing.assert_allclose(s, s_ref, atol=1e-5)


def test_mla_absorbed_decode_equals_expanded(params):
    p = params["layers"][3]
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 64))
    valid = jnp.ones((40,), bool)
    y, lat = ling.mla_prefill(CFG, p, x, valid)
    # decode position 39 of two "slots": the whole context, and its first 24
    got = ling.mla_decode(
        CFG, p, jnp.stack([x[39], x[23]]), jnp.asarray([39, 23]),
        ling.attend_latents(CFG, jnp.stack([lat, lat]),
                            jnp.asarray([40, 24])))
    np.testing.assert_allclose(got, jnp.stack([y[39], y[23]]), atol=1e-5)
    # and the expanded form is the reference's
    w = ProgramWeightsLing(params).layer(3)
    want = ref.mla(ref.spec_of(HP), x, w, jnp.arange(40))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_the_paged_kernel_attends_latents_as_the_gather_does(engine, params):
    """On a TPU the decode step's latent attention is ops/paged_attention's
    kernel with the latents as one KV head that is its own value: here in
    the Pallas interpreter, against the XLA gather, two requests in flight."""
    from ray_tpu.ops import paged_attention

    a, b = prompt(61, 100), prompt(62, 70)
    want = serve(engine, [a, b], max_tokens=6)
    paged_attention._INTERPRET = True
    try:
        eng = PagedEngine(CFG, params, ECFG)
        assert eng.stats()["decode_attention"] == paged_attention.KERNEL
        got = serve(eng, [a, b], max_tokens=6)
    finally:
        paged_attention._INTERPRET = False
    assert [g["token_ids"] for g in got] == [w["token_ids"] for w in want]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_routing_equals_the_reference(params, seed):
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed), (64, 64))
    experts, weights_, bits, _ = ling.route(CFG, p, x)
    e_ref, w_ref, kept = ref.route(ref.spec_of(HP), x, p["router"],
                                   p["router_bias"])
    np.testing.assert_array_equal(experts, e_ref)
    np.testing.assert_allclose(weights_, w_ref, atol=1e-6)
    np.testing.assert_array_equal(
        (np.asarray(bits)[:, None] >> np.arange(4)) & 1, kept)


def test_the_four_shares_add_up(params, weights):
    """Held 0-3, 4-7, 8-11, 12-15, the shared expert counted once = the whole
    layer of the uncut reference."""
    p = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    live = jnp.ones((48,), bool)
    total = jnp.zeros_like(x)
    held_pairs = 0
    for share in range(4):
        cfg = dataclasses.replace(CFG, held_start=4 * share, n_held=4)
        mine = {**p, **{k: p[k][4 * share:4 * share + 4]
                        for k in ("e_w1", "e_w3", "e_w2")}}
        y, _, counters, _ = ling.moe_held(cfg, mine, x, live,
                                          shared=share == 0)
        total = total + y
        held_pairs += int(counters[1])
        assert int(counters[0]) == 48 * 2
    assert held_pairs == 48 * 2            # every pair lands on one share
    want, _, _ = ref.moe(ref.spec_of(HP), x, weights.layer(2),
                         lambda lo, hi: weights.experts(2, lo, hi))
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("path", [grouped_ffn.XLA, grouped_ffn.KERNEL])
def test_a_decode_steps_rows_through_the_grouped_swiglu_equal_the_reference(
        params, weights, monkeypatch, path):
    """64 rows, a decode step's: 128 pairs, an expert holding a handful,
    four of the sixteen held so that most pairs sort past the held rows.
    Through the twin, which is what runs off the TPU, and through the kernel
    (in the interpreter)."""
    monkeypatch.setattr(grouped_ffn, "_INTERPRET", path == grouped_ffn.KERNEL)
    p = params["layers"][1]
    cfg = dataclasses.replace(CFG, held_start=4, n_held=4)
    mine = {**p, **{k: p[k][4:8] for k in ("e_w1", "e_w3", "e_w2")}}
    assert grouped_ffn.ffn_path(mine["e_w1"]) == path
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    live = jnp.arange(64) % 7 != 3            # a few empty slots
    y, _, counters, _ = ling.moe_held(cfg, mine, x, live)
    assert 0 < int(counters[1]) < int(counters[0]) == 2 * int(live.sum())
    want, _, _ = ref.moe(ref.spec_of(HP)._replace(held_start=4, held=4), x,
                         weights.layer(1),
                         lambda lo, hi: weights.experts(1, 4 + lo, 4 + hi))
    np.testing.assert_allclose(y[live], np.asarray(want)[np.asarray(live)],
                               atol=1e-5)


def test_engine_prefill_then_decode_equals_the_reference(engine, weights):
    """Prompts that cross block (16) and chunk (64) edges, 32 tokens through
    the state and the latent pool, two lengths in one batch."""
    prompts = [prompt(11, 70), prompt(12, 129)]
    for p, out in zip(prompts, serve(engine, prompts)):
        assert out["routing"].shape == (3, len(p) + 31, 3)
        g = ref.teacher_forced_gaps(HP, weights, p, out["token_ids"],
                                    out["routing"], 256)
        assert g["argmax_equal"] == 32 and max(g["gaps"]) < 1e-4
        assert g["routing"]["expert_steps"] == 0.0


def test_requests_in_one_batch_do_not_touch_each_other(engine):
    a, b = prompt(21, 200), prompt(22, 90)
    together = serve(engine, [a, b])
    alone_a, = serve(engine, [a])
    alone_b, = serve(engine, [b])
    assert together[0]["token_ids"] == alone_a["token_ids"]
    assert together[1]["token_ids"] == alone_b["token_ids"]


def test_a_reused_slot_starts_from_a_zero_state(engine, params):
    first, = serve(engine, [prompt(31, 100)])      # slot 0, then released
    second, = serve(engine, [prompt(32, 90)])      # slot 0 again
    fresh, = serve(PagedEngine(CFG, params, ECFG), [prompt(32, 90)])
    assert second["token_ids"] == fresh["token_ids"]
    assert first["token_ids"] != second["token_ids"]


def test_prefix_cache_is_refused_with_recurrent_layers(params):
    with pytest.raises(ValueError, match="recurrent"):
        PagedEngine(CFG, params, dataclasses.replace(ECFG, prefix_cache=True))


# the loop's stalls and its account of its own time: every family's engine
LOOP_KEYS = ("loop_stalls", "loop_stall_s", "loop_stall_admit_s",
             "loop_stall_last_at", "loop_turn_s", "loop_wait_s", "loop_idle_s",
             "turns_unwaited", "turn_unwaited_s")


def test_stats_count_the_experts(engine):
    serve(engine, [prompt(41, 80)], max_tokens=8)
    s = engine.stats()
    for key in ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
                "moe_load_max", "state_bytes", "latent_positions_live",
                *LOOP_KEYS, "steps_w0", "turn_s_w0"):
        assert key in s
    # no chunk ladder: every fetched step is a decode step
    assert s["steps_w0"] == s["steps"] and s["turn_s_w0"] == s["loop_turn_s"]
    assert 0 < s["moe_pairs_held"] <= s["moe_pairs_routed"]
    assert s["moe_experts_touched"] <= s["moe_pairs_held"]
    assert s["state_bytes"] == engine.state.nbytes + engine.tails.nbytes
    assert s["prefix_cache"] is None


def test_check_prefill_holds_the_step_to_the_forward_pass(engine):
    out = engine.check_prefill(prompt(51, 77))
    assert out["finite"] and out["argmax_equal"]
    assert out["max_abs_diff"] < 1e-4


def test_a_llama_engine_allocates_no_state_and_keeps_its_stats():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    eng = PagedEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                      dataclasses.replace(ECFG, prefix_cache=False))
    assert not hasattr(eng, "state") and not hasattr(eng, "latents")
    assert set(eng.stats()) == {
        "steps", "tokens_out", "free_blocks", "blocks_in_use", "active_slots",
        "mid_decode_admissions", "prefix_cache", "attn_positions_live",
        "attn_positions_dense", "attn_positions_shared", "decode_attention",
        "prefill_chunks",
        "prefill_chunk_tokens", "prefill_chunk_pad_tokens", "steps_with_chunk",
        "chunk_overtakes", "steps_ahead", "rows_dropped", "admissions",
        "admit_host_s", *LOOP_KEYS,
        *(f"{name}{w}" for name in ("steps_w", "turn_s_w")
          for w in (0, *chunk_ladder(ECFG)))}


def test_llm_config_resolves_the_family():
    from ray_tpu.llm import LLMConfig

    cfg, p = LLMConfig(model="ling:tiny",
                       model_overrides={"n_held": 8}).build_model()
    assert isinstance(cfg, ling.LingConfig) and cfg.n_held == 8
    assert p["layers"][1]["e_w1"].shape == (8, 64, 32)
    cfg, _ = LLMConfig(model="tiny").build_model()
    assert type(cfg).__name__ == "LlamaConfig"


def test_the_expert_bias_is_balanced_as_training_would():
    """`seeded_params`, what a server without a checkpoint serves, moves each
    expert layer's bias towards equal load on seeded tokens
    (`balance_expert_bias`; `init_params` leaves it as drawn): on other
    tokens the fullest expert's load falls, and with it how far a chip's
    share of the pairs swings with the seed."""
    raw = ling.init_params(CFG, jax.random.PRNGKey(3))
    # a router collapsed onto one group, as random weights at the published
    # widths are: every token prefers experts 0-3
    raw["layers"][1]["router_bias"] = raw["layers"][1]["router_bias"].at[
        :4].add(0.5)
    bal = jax.jit(lambda p, k: ling.balance_expert_bias(CFG, p, k))(
        raw, jax.random.PRNGKey(4))
    toks = jnp.asarray(prompt(71, 128)) % 95 + 32
    live = jnp.ones((128,), bool)

    @jax.jit
    def fullest(p):
        """The first expert layer's fullest expert over its mean load."""
        h = p["tok_emb"][toks]
        for (attn, _), lp in list(zip(CFG.kinds(), p["layers"]))[:2]:
            x = ling.rms_norm(h, lp["ln1"], CFG.norm_eps)
            h = h + ling.kda_prefill(CFG, lp, x, live)[0]
            x = ling.rms_norm(h, lp["ln2"], CFG.norm_eps)
            if "router" in lp:
                experts = ling.route(CFG, lp, x)[0]
                load = jnp.sum(jax.nn.one_hot(experts.reshape(-1), 16), 0)
                return load.max() / load.mean()
            h = h + ling.ffn(CFG, lp, x, live)[0]

    assert np.array_equal(raw["layers"][1]["router"],
                          bal["layers"][1]["router"])
    assert float(fullest(raw)) > 4.0 and float(fullest(bal)) < 2.0


def test_the_decode_steps_router_and_state_are_the_float32_ones(engine,
                                                               weights):
    """`check_routing(mechanisms=True)` hands out what each decode step's
    router and recurrence computed from; the reference on the same inputs
    arrives at the same scores and the same state, and reads the precision
    below (a bf16 router, a state rounded to bf16) far off."""
    p = prompt(81, 70)
    out, = [asyncio.run(_replay(engine, p, 40))]
    plain, = serve(engine, [p], max_tokens=40)
    assert out["token_ids"] == plain["token_ids"]
    np.testing.assert_array_equal(out["routing"], plain["routing"])
    assert out["router_s"].shape == (39, 3, 16)
    assert out["q"].shape == (39, 3, 4, 16) and out["beta"].shape == (39, 3, 4)
    assert out["state0"].shape == out["state"].shape == (3, 4, 16, 16)
    got = ref.mechanism_readings(out, weights.routers())
    assert got["state_steps"] == 39
    assert got["router_f32_steps"] < 1.0 < 100 < got["router_f32_steps_bf16"]
    assert got["state_error"] < 1e-5 < 1e-3 < got["state_error_bf16"]
    # the slot is handed back: the next probed request is admitted
    assert engine._probe_slot is None


async def _replay(engine, p, n):
    engine._pending = engine._loop_task = None
    return await engine.check_routing(p, n, mechanisms=True)


def test_stats_count_the_loops_stalled_turns(engine, monkeypatch):
    """A turn of the loop (sweep, admissions, one decode step) that takes
    longer than `STALL_TURN_S` is counted with its seconds and the part of
    them before the step, so that a run's counters tell a stall from a
    slower step; with the limit at zero every turn is one."""
    from ray_tpu.llm import _engine

    serve(engine, [prompt(90, 40)], max_tokens=4)      # compiles: a stall
    before = engine.stats()
    serve(engine, [prompt(91, 40)], max_tokens=4)
    assert engine.stats()["loop_stalls"] == before["loop_stalls"]
    monkeypatch.setattr(_engine, "STALL_TURN_S", 0.0)
    serve(engine, [prompt(92, 40)], max_tokens=4)
    after = engine.stats()
    assert after["loop_stalls"] == before["loop_stalls"] + 3   # decode steps
    assert 0 < after["loop_stall_admit_s"] < after["loop_stall_s"]
    assert after["loop_stall_last_at"] > before["loop_stall_last_at"]

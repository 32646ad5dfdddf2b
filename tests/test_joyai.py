"""The JoyAI-LLM-Flash family (models/joyai.py, llm/_joyai_steps.py: latent
attention with a low-rank query in every layer over a paged pool of latents,
prompts as chunks against it, ungrouped sigmoid routing) against the plain
float32 reference (benchmark/lib/reference_joyai.py), at a tiny size on the
CPU: hidden 64, 4 heads of 16 + 8, a query rank of 48, a latent of 32 + 8,
16 experts top-2, one dense layer and three expert layers. Blocks of 16,
chunks of 32 and 64 rows.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_joyai as ref
from benchmark.runners._inside_joyai import ProgramWeightsJoyAI
from ray_tpu.llm import _joyai_steps, step_set
from ray_tpu.llm._engine import STEP_SET, WHOLE_PROMPT, EngineConfig, PagedEngine
from ray_tpu.models import joyai, ling
from ray_tpu.ops import paged_attention

CFG = joyai.JoyAIConfig.tiny()
HP = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=1e4, rope_interleave=True, rms_norm_eps=1e-6,
    first_k_dense_replace=1, num_hidden_layers=4, moe_intermediate_size=32,
    n_routed_experts=16, num_experts_per_tok=2, n_group=1, topk_group=1,
    routed_scaling_factor=2.5, vocab_size=512)
ECFG = EngineConfig(max_num_seqs=3, kv_block_size=16, num_kv_blocks=64,
                    max_model_len=256)
SPEC = ref.spec_of(HP)
# float32 on both sides: what is left is the order of summation
GAP = 2e-4


@pytest.fixture(scope="module")
def params():
    return jax.jit(lambda k: joyai.seeded_params(CFG, k))(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return ProgramWeightsJoyAI(params)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


def reference_logits(weights, tokens, positions, hp=HP, **kw):
    n = -(-len(tokens) // 64) * 64
    return ref.logits_at(hp, weights, list(tokens) + [0] * (n - len(tokens)),
                         positions, **kw)


def greedy_against_the_reference(weights, p, toks):
    lg = reference_logits(weights, p + toks,
                          [len(p) - 1 + i for i in range(len(toks))])
    return float(np.max(lg.max(-1) - lg[np.arange(len(toks)), toks]))


def normed_inputs(n=24, seed=3):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, CFG.dim), jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))


def test_the_step_set_is_blocks_alone_and_the_prefix_cache_is_on(params):
    steps = step_set(CFG)
    assert steps is _joyai_steps
    assert {n for n in dir(steps) if n in STEP_SET} == set(STEP_SET) - set(
        WHOLE_PROMPT)
    assert (steps.SLOT_STATE, steps.NO_PREFIX_CACHE, steps.SNAPSHOT_STATE,
            steps.SNAPSHOT_POLICY) == (None,) * 4
    assert steps.CACHE_NAMES == ("latents",)
    assert steps.chunk_ladder(ECFG) == (32, 64)
    engine = PagedEngine(CFG, params, ECFG)
    assert engine._prefix_cache is not None and engine._prefill is None
    assert engine.latents.shape == (4, 65, 16, 1, 128)
    with pytest.raises(ValueError, match="prompts run as chunks"):
        engine.check_prefill([1, 2, 3])
    published = joyai.JoyAIConfig()
    assert (published.latent_dim, published.latent_width) == (576, 640)
    assert jax.eval_shape(lambda: _joyai_steps.alloc_cache(
        published, dataclasses.replace(ECFG, num_kv_blocks=6144))
    )[0].shape == (40, 6145, 16, 1, 640)


def test_assumed_rotary_in_adjacent_pairs():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 3, 8), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 4000])
    got = np.asarray(ling.rope_pairs(x, pos, 1e4))
    # the complex form: (x_2i + j x_2i+1) e^(j pos theta^(-2i/hd))
    z = np.asarray(x[..., 0::2]) + 1j * np.asarray(x[..., 1::2])
    ang = np.asarray(pos, np.float64)[:, None, None] * 1e4 ** (
        -np.arange(0, 8, 2) / 8)
    want = z * np.exp(1j * ang)
    np.testing.assert_allclose(got[..., :4], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 4:], want.imag, atol=1e-5)
    np.testing.assert_allclose(
        got, np.asarray(ref.rope_pairs(x, pos, 1e4)), atol=1e-6)
    # not the rotation in halves, which the Ling family keeps
    assert np.abs(got - np.asarray(ling.rope_half(x, pos, 1e4))).max() > 0.1
    assert ling.LingConfig().rope_interleave is False and CFG.rope_interleave


def test_assumed_low_rank_query_is_normed(params, weights):
    p = joyai.layer_params(params, 1)
    assert p["wqa"].shape == (64, 48) and p["wqb"].shape == (48, 4 * 24)
    assert "wq" not in p and "wg" not in p
    x, pos = normed_inputs(), jnp.arange(24)
    q_nope, q_r = ling._mla_q(CFG, p, x, pos)
    c_q = x @ p["wqa"]
    c_q = c_q * jax.lax.rsqrt(jnp.mean(c_q * c_q, -1, keepdims=True) + 1e-6)
    q = (c_q * p["q_norm"] @ p["wqb"]).reshape(24, 4, 24)
    np.testing.assert_allclose(q_nope, q[..., :16], atol=1e-5)
    np.testing.assert_allclose(
        q_r, ling.rope_pairs(q[..., 16:], pos, 1e4), atol=1e-5)
    # the whole block, expanded, against the reference's
    got, lat = ling.mla_prefill(CFG, p, x, jnp.ones((24,), bool))
    want, want_lat = ref.mla(SPEC, x, weights.layer(1), pos)
    np.testing.assert_allclose(got, want, atol=GAP)
    np.testing.assert_allclose(lat, want_lat, atol=1e-5)
    without = ref.mla(SPEC, x, weights.layer(1), pos, plant="no_q_norm")[0]
    assert np.abs(np.asarray(without) - np.asarray(want)).max() > 0.01


def test_absorbed_decode_equals_the_expanded_reference(params, weights):
    p = joyai.layer_params(params, 2)
    x, pos = normed_inputs(24, seed=5), jnp.arange(24)
    want = np.asarray(ref.mla(SPEC, x, weights.layer(2), pos)[0])
    lat = ling.mla_latents(CFG, p, x, pos)
    # every position as a decode row over the latents up to itself
    context = jnp.broadcast_to(lat[None], (24, 24, lat.shape[1]))
    got = ling.mla_decode(CFG, p, x, pos,
                          ling.attend_latents(CFG, context, pos + 1))
    np.testing.assert_allclose(got, want, atol=GAP)


def test_chunk_latent_attention_reads_cached_blocks_and_its_own_rows():
    rng = np.random.default_rng(0)
    W, rank, H, C, bs = 128, 32, 4, 32, 16
    pool = jnp.asarray(rng.normal(size=(2, 9, bs, 1, W)), jnp.float32)
    pool = pool.at[..., 40:].set(0.0)
    row = jnp.asarray([3, 1, 7, 5, 0, 0, 0, 0], jnp.int32)
    q = jnp.asarray(rng.normal(size=(C, H, W)), jnp.float32).at[..., 40:].set(0)
    start, n = 20, 29                      # positions 20..48 of 49
    qpos = start + jnp.arange(C)
    got = paged_attention.chunk_latent_attention(
        q, pool, 1, row, qpos, start + n, rank, tile=32)
    lat = np.asarray(pool[1][row[:4]]).reshape(64, W)
    s = np.einsum("chw,kw->chk", np.asarray(q), lat)
    s = np.where(np.arange(64)[None, None] <= np.asarray(qpos)[:, None, None],
                 s, -np.inf)
    pr = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("chk,kr->chr", pr / pr.sum(-1, keepdims=True),
                     lat[:, :rank])
    np.testing.assert_allclose(np.asarray(got)[:n], want[:n], atol=1e-5)


def test_assumed_ungrouped_sigmoid_routing(params, weights):
    p, w = joyai.layer_params(params, 1), weights.layer(1)
    x = normed_inputs(40, seed=7)
    experts, wts, bits, s = ling.route(CFG, p, x)
    own, s_ref, sb = ref.route(SPEC, x, w["gate"], w["e_score_correction_bias"])
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(own, -1))
    np.testing.assert_allclose(s, s_ref, atol=1e-6)
    np.testing.assert_allclose(
        np.sort(wts, -1), np.sort(ref.combine_weights(SPEC, s_ref, own), -1),
        atol=1e-6)
    assert np.all(np.asarray(bits) == 1)        # the one group, always kept
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 2.5, atol=1e-5)
    # the bias moves the selection, not the weights
    top = np.argsort(-np.asarray(sb), -1)[:, :2]
    np.testing.assert_array_equal(np.sort(top, -1), np.sort(experts, -1))


def test_sixteen_shares_add_up_to_the_uncut_layer(params, weights):
    """16 experts in shares of 1 (the cell's 256 in shares of 16): each
    share's expert part, the shared expert counted once, is the whole."""
    p = joyai.layer_params(params, 3)
    x, live = normed_inputs(32, seed=9), jnp.ones((32,), bool)
    whole = ling.moe_held(CFG, p, x, live)[0]
    total, pairs = 0.0, 0
    for e in range(16):
        cut = dataclasses.replace(CFG, held_start=e, n_held=1)
        pe = {**p, **{k: p[k][e:e + 1] for k in ("e_w1", "e_w3", "e_w2")}}
        y, _, counters, _ = ling.moe_held(cut, pe, x, live, shared=(e == 0))
        total, pairs = total + y, pairs + int(counters[1])
    np.testing.assert_allclose(total, whole, atol=GAP)
    assert pairs == 32 * 2
    want = ref.moe(SPEC, x, weights.layer(3),
                   lambda lo, hi: weights.experts(3, lo, hi))[0]
    np.testing.assert_allclose(whole, want, atol=GAP)


def test_assumed_expert_bias_is_balanced(params):
    raw = joyai.init_params(CFG, jax.random.split(jax.random.PRNGKey(0))[0])
    assert not np.allclose(raw["layers"]["router_bias"],
                           params["layers"]["router_bias"])
    for k in ("router", "e_w1", "wqa"):
        np.testing.assert_array_equal(raw["layers"][k], params["layers"][k])
    # on the tokens the balancing routed: the fullest expert's load over the
    # mean, the worst layer's
    toks = ling.balance_tokens(jax.random.split(jax.random.PRNGKey(0))[1])
    T = toks.shape[0]

    def worst_load(tree):
        seen = []
        h = tree["tok_emb"][toks]
        for p in joyai.each_layer(CFG, tree):
            h, x = joyai._whole_layer(CFG, p, h, jnp.ones((T,), bool))
            if "router" in p:
                experts = ling.route(CFG, p, x)[0]
                seen.append(np.bincount(np.asarray(experts).ravel(),
                                        minlength=16).max() / (T * 2 / 16))
            h = h + ling.ffn(CFG, p, x, jnp.ones((T,), bool))[0]
        return max(seen)

    assert worst_load(params) < 1.2 < worst_load(raw)


def test_forward_equals_the_reference(params, weights):
    toks = prompt(2, 100)
    padded = np.zeros(128, np.int32)
    padded[:100] = toks
    got = jax.jit(lambda t: joyai.forward(CFG, params, t, 100))(
        jnp.asarray(padded))
    want = reference_logits(weights, toks, list(range(100)))
    np.testing.assert_allclose(np.asarray(got)[:100], want, atol=GAP)
    for plant in ("no_q_norm", "rope_halves"):
        low = reference_logits(weights, toks, list(range(100)), plant=plant)
        assert np.abs(low - want).max() > 0.02, plant


@pytest.fixture(scope="module")
def served(params):
    """A prompt in chunks then decode; a second request resumed from the
    first's shared blocks, warm and cold, with its mechanisms recorded."""
    engine = PagedEngine(CFG, params, ECFG)
    system = prompt(20, 100)
    first, second = system + prompt(21, 70), system + prompt(22, 45)

    async def run():
        a = [t async for t in engine.generate_stream(first, max_tokens=8)]
        warm = await engine.check_routing(second, 8, mechanisms=True)
        cold = await engine.check_routing(second, 8, mechanisms=True,
                                          cold=True)
        return a, warm, cold

    a, warm, cold = asyncio.run(run())
    return dict(engine=engine, first=first, second=second, a=a, warm=warm,
                cold=cold)


def test_chunks_then_decode_then_a_resumed_request_equal_the_reference(
        served, weights):
    # 170 tokens: chunks of 64, 64, 64 (42 real) from position 0
    assert greedy_against_the_reference(
        weights, served["first"], served["a"]) < GAP
    warm = served["warm"]
    # the second request found the system prompt's 6 whole blocks
    assert warm["resume_from"] == 96
    assert greedy_against_the_reference(
        weights, served["second"], warm["token_ids"]) < GAP
    stats = served["engine"].stats()
    assert stats["prefix_cache"]["block_hits"] >= 6
    assert stats["prefill_chunks"] >= 3 + 1 + 3
    assert stats["latent_positions_read"] == stats["latent_positions_live"] > 0
    assert stats["chunk_latents_read"] > 0
    assert stats["latent_bytes"] == 4 * 65 * 16 * 128 * 4


def test_a_resumed_request_returns_the_cold_runs_tokens_and_mechanisms(served):
    warm, cold = served["warm"], served["cold"]
    assert cold["resume_from"] == 0 and warm["resume_from"] == 96
    assert warm["token_ids"] == cold["token_ids"]
    # the positions both computed: the same experts, the same router inputs
    np.testing.assert_array_equal(warm["routing"], cold["routing"][:, 96:])
    for key in ("router_x", "router_s"):
        np.testing.assert_allclose(warm[key], cold[key], atol=1e-5)
    assert warm["state0"] is None and warm["state"] is None


def test_the_check_holds_the_pool_to_the_references_latents(served, weights):
    from benchmark.lib.reference_joyai import conversation_gaps
    from ray_tpu.llm._prefix_cache import chain_keys

    engine, p, r = served["engine"], served["second"], served["cold"]
    blocks = engine._prefix_cache.match(chain_keys(p, 16))
    engine._prefix_cache.cancel_match(blocks)
    assert len(blocks) == len(p) // 16

    def cached(layer, spoil=None):
        rows = np.asarray(engine.latents[layer, np.asarray(blocks)])
        rows = rows.reshape(len(blocks) * 16, -1)[:, :40].copy()
        if spoil == "stale" and layer == 2:
            rows[32:48] = rows[48:64]
        if spoil == "float8":
            rows = ref.in_float8(rows)
        return rows

    hp = {**HP, "router_num_experts": 16}
    judged = [(len(p) - 1 + i, t) for i, t in enumerate(r["token_ids"])]
    args = (hp, weights, p + r["token_ids"], judged,
            r["routing"][:, : len(p) + 7])
    g = conversation_gaps(*args, cached, 64, second_readings=True)
    assert max(g["gaps"]) < GAP and g["routing"]["expert_steps"] == 0.0
    assert g["cache_error"] < 1e-5 and g["cache_row_error"] < 1e-5
    assert g["cache_error_float8"] > 0.02 and max(g["gaps_float8"]) > 0.01
    stale = conversation_gaps(*args, lambda l: cached(l, "stale"), 64)
    assert stale["cache_row_error"] > 0.5 and stale["cache_worst_layer"] == 2
    low = conversation_gaps(*args, lambda l: cached(l, "float8"), 64)
    assert low["cache_error"] > 0.02
    halves = conversation_gaps(*args, cached, 64, plant="rope_halves")
    assert halves["cache_error"] > 0.02 and max(halves["gaps"]) > 0.01


def test_sessions_of_one_prefix_read_shared_blocks(served):
    """The module's engine again: its cache holds `first`'s prefix."""
    engine, before = served["engine"], served["engine"].stats()
    system = served["first"][:96]

    def ask(i):
        return _drain(engine.generate_stream(system + prompt(31 + i, 20),
                                             max_tokens=12))

    async def run():
        await asyncio.gather(ask(1), ask(2))

    asyncio.run(run())
    stats = engine.stats()
    shared = stats["attn_positions_shared"] - before["attn_positions_shared"]
    live = stats["attn_positions_live"] - before["attn_positions_live"]
    # whole blocks of the 6 the prefix has, while both decode
    assert 0 < shared < live and shared % (6 * 16) == 0


async def _drain(stream):
    return [t async for t in stream]


def test_transferred_blocks_seed_a_sequence():
    inject = _joyai_steps.make_kv_inject(CFG, ECFG)
    (pool,) = _joyai_steps.alloc_cache(CFG, ECFG)
    blocks = jnp.ones((4, 2, 16, 1, 128), jnp.float32)
    (latents,) = inject(pool, jnp.asarray([5, 9]), blocks)
    assert float(latents[:, 5].min()) == 1.0 == float(latents[:, 9].max())
    assert float(jnp.abs(latents[:, 6]).max()) == 0.0


def test_the_family_is_served_by_its_name():
    from ray_tpu.llm import MODEL_FAMILIES, LLMConfig

    assert MODEL_FAMILIES["joyai"][2] == "seeded_params"
    cfg, params = LLMConfig(model="joyai:tiny", seed=3,
                            model_overrides={"n_held": 4, "held_start": 8}
                            ).build_model()
    assert isinstance(cfg, joyai.JoyAIConfig) and cfg.n_held == 4
    assert params["layers"]["e_w1"].shape == (3, 4, 64, 32)
    assert params["layers"]["router"].shape == (3, 64, 16)
    assert len(params["dense"]) == 1 and "router" not in params["dense"][0]

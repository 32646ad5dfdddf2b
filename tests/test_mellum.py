"""The Mellum 2 family (models/mellum.py, llm/_mellum_steps.py: a block pool
under the full layers, a ring a slot under the window layers) against the
plain float32 reference (benchmark/lib/reference_mellum.py), at a tiny size
on the CPU: hidden 64, 8 query heads on 2 KV heads of 16 (dim / heads is 8),
a window of 8, 8 experts top-2, 8 layers = (window x 3, full) twice. Blocks
of 4, chunks of 16 and 32 rows, a ring of 40 positions.
"""

import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ling as rl
from benchmark.lib import reference_mellum as ref
from benchmark.runners._inside_mellum import ProgramWeightsMellum
from ray_tpu.llm import _mellum_steps, step_set
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.models import ling, mellum, solar

CFG = mellum.MellumConfig.tiny()
HP = dict(
    hidden_size=64, num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    sliding_window=8, rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, attention_bias=False, num_hidden_layers=8,
    vocab_size=512,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 2,
    mlp_layer_types=["sparse"] * 8,
    rope_parameters={
        "full_attention": dict(
            rope_type="yarn", rope_theta=10000.0, factor=4.0,
            original_max_position_embeddings=16, beta_fast=4.0, beta_slow=1.0,
            attention_factor=CFG.yarn_attention_factor),
        "sliding_attention": dict(rope_type="default", rope_theta=10000.0)})
ECFG = EngineConfig(max_num_seqs=3, kv_block_size=4, num_kv_blocks=96,
                    max_model_len=128)
SPEC = ref.spec_of(HP)
PUBLISHED = mellum.MellumConfig()
# float32 on both sides: what is left is the order of summation
GAP, ATTN = 1e-4, 1e-4


@pytest.fixture(scope="module")
def params():
    return mellum.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return ProgramWeightsMellum(params, 2 * 16)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


def serve(engine, prompts, max_tokens=8, together=False, **kw):
    """The prompts through the engine's loop: one after the other, or all
    in its queue at once."""
    async def go():
        engine._pending = engine._loop_task = None
        if together:
            return await asyncio.gather(*[
                engine.check_routing(p, n, **kw)
                for p, n in zip(prompts, max_tokens)])
        return [await engine.check_routing(p, max_tokens, **kw)
                for p in prompts]

    return asyncio.run(go())


def gaps(weights, p, out, **plants):
    return ref.teacher_forced_gaps(
        HP, weights, p, out["token_ids"], out["routing"], 64,
        attn_o=out.get("attn_o"), **plants)


@pytest.fixture(scope="module")
def served(params):
    """One request whose prompt crosses the window, the widest chunk and the
    ring's end (70 tokens: chunks of 32, 32 and 6 over a ring of 40), and
    then decodes past two more wraps of the ring."""
    engine = PagedEngine(CFG, params, ECFG)
    p = prompt(1, 70)
    out, = serve(engine, [p], 56, mechanisms=True)
    return engine, p, out


# --- the configuration and its rotaries ---------------------------------------


def test_the_layer_kinds_are_the_published_ones():
    assert CFG.kinds() == ["window"] * 3 + ["full"] + ["window"] * 3 + ["full"]
    kinds = PUBLISHED.kinds()
    assert len(kinds) == 28 and kinds.count("full") == 7
    assert all((k == "full") == (i % 4 == 3) for i, k in enumerate(kinds))
    cell = mellum.MellumConfig(n_layers=8, layer_ids=tuple(range(8)))
    assert (cell.window_layers, cell.full_layers) == (6, 2)
    assert [ref.WINDOW if k == "window" else ref.FULL for k in CFG.kinds()
            ] == ref.layer_kinds(HP)


def test_head_dim_is_its_own_number():
    assert PUBLISHED.head_dim == 128 != PUBLISHED.dim // PUBLISHED.n_heads
    assert CFG.head_dim == 16 != CFG.dim // CFG.n_heads
    shapes = jax.eval_shape(
        lambda: mellum.init_params(CFG, jax.random.PRNGKey(0)))
    assert shapes["layers"][0]["wqkv"].shape == (64, (8 + 2 * 2) * 16)
    assert shapes["layers"][0]["wo"].shape == (8 * 16, 64)


def test_yarn_at_the_published_numbers():
    """`low` and `high` (the issue's 18 and 35), the frequencies on both
    sides of the ramp and the factor 0.1 ln 16 + 1."""
    assert mellum.yarn_range(PUBLISHED) == (18, 35)
    freq, factor = mellum.inv_freq(PUBLISHED, "full")
    plain, one = mellum.inv_freq(PUBLISHED, "window")
    assert one == 1.0 and factor == 1.2772588722239782
    assert math.isclose(factor, 0.1 * math.log(16) + 1, rel_tol=1e-15)
    i = np.arange(64)
    np.testing.assert_allclose(plain, 500000.0 ** (-2 * i / 128), rtol=1e-6)
    np.testing.assert_array_equal(freq[:19], plain[:19])         # kept
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-6)
    ramp = (i[19:35] - 18) / 17
    np.testing.assert_allclose(
        freq[19:35], plain[19:35] * ((1 - ramp) + ramp / 16), rtol=1e-6)
    # the reference's own writing of it arrives at the same
    published = ref.Spec(2304, 32, 4, 128, 1024, 5e5, 16.0, 8192, 32.0, 1.0,
                         factor, 1e-6, 64, 8)
    assert ref.yarn_range(published) == (18, 35)
    np.testing.assert_array_equal(ref.inv_freq(published, ref.FULL), freq)


def test_assumed_yarn_range_is_truncated_to_whole_dimensions():
    """`assumed`: `truncate` of YaRN's range. corr(32) = 18.08 and corr(1) =
    34.98: untruncated, dimension 18 would already be 0.5% along the ramp."""
    low, high = mellum.yarn_range(PUBLISHED)
    assert isinstance(low, int) and isinstance(high, int)
    freq, _ = mellum.inv_freq(PUBLISHED, "full")
    plain, _ = mellum.inv_freq(PUBLISHED, "window")
    assert freq[18] == plain[18] and freq[35] == np.float32(plain[35] / 16)


def test_assumed_rotary_in_halves():
    """`assumed`: channel i turns with channel i + hd / 2
    (`models/llama.py::apply_rope`'s convention), not with i + 1."""
    x = jnp.zeros((1, 1, 16)).at[0, 0, 0].set(1.0)
    got = np.asarray(mellum.rope(CFG, "window", x, jnp.asarray([1])))[0, 0]
    assert np.flatnonzero(np.abs(got) > 1e-6).tolist() == [0, 8]
    np.testing.assert_allclose(got[[0, 8]], [np.cos(1.0), np.sin(1.0)],
                               atol=1e-6)
    want = np.asarray(ref.rope(x, jnp.asarray([1]),
                               ref.inv_freq(SPEC, ref.WINDOW), 1.0))[0, 0]
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_the_attention_factor_is_on_q_and_k_alike():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 2, 16))
    pos = jnp.arange(5) + 20
    full = np.asarray(mellum.rope(CFG, "full", x, pos))
    np.testing.assert_allclose(
        np.linalg.norm(full, axis=-1),
        CFG.yarn_attention_factor * np.linalg.norm(x, axis=-1), rtol=1e-5)
    window = np.asarray(mellum.rope(CFG, "window", x, pos))
    np.testing.assert_allclose(np.linalg.norm(window, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


# --- the blocks against the reference -----------------------------------------


@pytest.mark.parametrize("layer,kind", [(0, "window"), (3, "full")])
def test_an_attention_block_equals_the_reference(params, weights, layer, kind):
    x = jax.random.normal(jax.random.PRNGKey(layer), (128, 64))
    got = mellum.attn_sequence(CFG, kind, params["layers"][layer], x,
                               jnp.arange(128) < 100)
    want, _ = ref.attention(
        SPEC, ref.layer_kinds(HP)[layer], x[:100], weights.layer(layer),
        reach=8 if kind == "window" else None)
    np.testing.assert_allclose(got[:100], want, atol=2e-5)


def test_assumed_q_and_k_are_normed_per_head(params, weights):
    """`assumed`: RMSNorm over each head's width with a learned weight of
    `head_dim`, before the rotary. The seeded weights are drawn U(1, 2) so
    that scores spread by a few units and a lost key shows."""
    p = params["layers"][0]
    assert p["q_norm"].shape == p["k_norm"].shape == (16,)
    assert 1.0 <= float(p["q_norm"].min()) and float(p["q_norm"].max()) <= 2.0
    x = 5.0 * jax.random.normal(jax.random.PRNGKey(3), (6, 64))
    q, k, _ = mellum.attn_project(CFG, "window", p, x, jnp.zeros((6,), jnp.int32))
    # position 0 turns nothing: what is left is the norm and its weight
    np.testing.assert_allclose(
        np.sqrt(np.mean(np.square(q / p["q_norm"]), axis=-1)), 1.0, atol=1e-3)
    np.testing.assert_allclose(
        np.sqrt(np.mean(np.square(k / p["k_norm"]), axis=-1)), 1.0, atol=1e-3)
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 4, axis=1)) / 4.0
    assert float(scores.std()) > 1.5


def test_assumed_router_is_a_float32_softmax_before_the_top_k(params):
    """`assumed`: p = softmax(z) over all the experts in float32, then the
    top-k, weights renormalised over the chosen (`norm_topk_prob`)."""
    p = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    experts, weights_, bits, probs = mellum.route(CFG, p, x)
    assert probs.dtype == weights_.dtype == jnp.float32
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-6)
    want_e, want_w, want_p = ref.route(SPEC, x, p["router"])
    np.testing.assert_array_equal(experts, want_e)
    np.testing.assert_allclose(weights_, want_w, atol=1e-6)
    np.testing.assert_allclose(probs, want_p, atol=1e-7)
    np.testing.assert_allclose(weights_.sum(-1), 1.0, atol=1e-6)
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(experts), 1)
    np.testing.assert_allclose(weights_, chosen / chosen.sum(-1, keepdims=True),
                               atol=1e-6)
    assert set(np.asarray(bits).tolist()) == {1}
    # a bf16 router is thousands of float32 steps away
    low = mellum.router_probs(CFG, p, x, jnp.bfloat16)
    assert ref.router_float32_steps(x, probs, p["router"]) < 8
    assert ref.router_float32_steps(x, low, p["router"]) > 1000


def test_assumed_no_mtp_head_and_no_shared_expert(params):
    """`assumed`: the row's `config` has no key for the MTP head that
    `described_as` names, and no shared expert: neither has a parameter."""
    assert set(params) == {"tok_emb", "layers", "norm", "lm_head"}
    assert set(params["layers"][0]) == {
        "ln1", "ln2", "wqkv", "q_norm", "k_norm", "wo", "router", "e_w1",
        "e_w3", "e_w2"}
    assert params["lm_head"].shape == (64, 512)      # untied


def test_moe_held_with_the_softmax_routing_equals_the_dense_sum(params, weights):
    p = params["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    live = jnp.arange(48) < 40
    y, routing, counters, probs = mellum.moe(CFG, p, x, live)
    experts, w, _ = ref.route(SPEC, x, p["router"])
    e = weights.experts(1, 0, 8)
    want = rl.routed_block(x, experts, w, 0, e["w_gate"], e["w_up"], e["w_down"])
    np.testing.assert_allclose(y[:40], want[:40], atol=2e-5)
    assert not np.asarray(y[40:]).any()
    assert np.asarray(routing[40:] == -1).all()
    assert counters.tolist()[:2] == [80, 80]


@pytest.mark.parametrize("family", ["ling", "solar"])
def test_the_other_families_routing_is_bit_for_bit_what_it_was(family):
    """`moe_held` without a `routing` is `moe_held` with Ling's `route`,
    and traces the same program."""
    model = ling if family == "ling" else solar
    cfg = (ling.LingConfig if family == "ling" else solar.SolarConfig).tiny()
    params = model.init_params(cfg, jax.random.PRNGKey(1))
    p = next(q for q in params["layers"] if "router" in q)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.dim))
    live = jnp.arange(24) < 20
    plain = ling.moe_held(cfg, p, x, live)
    given = ling.moe_held(cfg, p, x, live, routing=ling.route)
    for a, b in zip(plain, given):
        np.testing.assert_array_equal(a, b)
    assert str(jax.make_jaxpr(lambda x: ling.moe_held(cfg, p, x, live))(x)) \
        == str(jax.make_jaxpr(lambda x: ling.moe_held(
            cfg, p, x, live, routing=ling.route))(x))


def test_forward_equals_the_reference(params, weights):
    toks = prompt(1, 100)
    padded = np.zeros(512, np.int32)
    padded[:100] = toks
    got = jax.jit(lambda p, t: mellum.forward(CFG, p, t, 100))(
        params, jnp.asarray(padded))
    want = ref.logits_at(HP, weights, toks, list(range(100)))
    np.testing.assert_allclose(np.asarray(got)[:100], want, atol=2e-4)


# --- the engine: chunks, then decode, through the pool and the ring ------------


def test_the_engine_equals_forward_and_the_reference_past_the_rings_wraps(
        served, params, weights):
    engine, p, out = served
    assert engine._ladder == (16, 32) and engine.wk.shape == (6, 3, 40, 2, 16)
    assert engine.kc.shape == (2, 97, 4, 2, 16)
    assert not engine._resumes and engine._prefix_cache is None
    toks = out["token_ids"]
    assert len(toks) == 56 and len(p) + len(toks) > 3 * 40
    # models/mellum.py::forward, fed what was served, says the same tokens
    seq = np.zeros(512, np.int32)
    seq[:125] = p + toks[:-1]
    logits = np.asarray(jax.jit(lambda t: mellum.forward(
        CFG, params, t, 125))(jnp.asarray(seq)))
    assert logits[69:125].argmax(-1).tolist() == toks
    # and the reference, with every layer's attention at every decode step
    g = gaps(weights, p, out)
    assert max(g["gaps"]) <= GAP and g["argmax_equal"] == 56
    assert g["routing"] == {"expert_steps": 0.0, "same_experts": 1.0}
    assert g["attn_window_error"] <= ATTN and g["attn_full_error"] <= ATTN
    readings = ref.router_readings(out, weights.routers())
    assert readings["router_f32_steps"] < 8
    assert readings["router_f32_steps_bf16"] > 1000


@pytest.mark.parametrize("plant", sorted(ref.PLANTS))
def test_a_planted_departure_fails_the_check(served, weights, plant):
    """Window 7 and 9 where 8 passes, the window layers run full, the full
    layers windowed, the plain rotary on the full layers, an
    `attention_factor` of 1, keys and values in float8: each reads over the
    limits the served path keeps by orders of magnitude."""
    _, p, out = served
    g = gaps(weights, p, out, **ref.PLANTS[plant])
    kind = {"window_layers_full": "window", "window_minus_1": "window",
            "window_plus_1": "window"}.get(plant, "full")
    assert g[f"attn_{kind}_error"] > 100 * ATTN
    assert max(g["gaps"]) > 100 * GAP


@pytest.mark.parametrize("window", [7, 9])
def test_forward_with_another_window_parts_from_the_served_tokens(
        served, params, window):
    _, p, out = served
    seq = np.zeros(512, np.int32)
    seq[:125] = p + out["token_ids"][:-1]
    at = lambda w: np.asarray(jax.jit(lambda t: mellum.forward(
        CFG, params, t, 125, window=w))(jnp.asarray(seq)))[69:125]
    assert np.abs(at(window) - at(8)).max() > 0.05


@pytest.mark.parametrize("n", [5, 8, 9, 31, 33, 41, 64])
def test_a_prompt_of_any_length_round_the_window_and_the_rings_end(
        params, weights, n):
    """Inside the window, at its edge, across it; across a chunk's end;
    across the ring's end."""
    engine = PagedEngine(CFG, params, ECFG)
    p = prompt(n, n)
    out, = serve(engine, [p], 6, mechanisms=True)
    g = gaps(weights, p, out)
    assert max(g["gaps"]) <= GAP
    assert g["attn_window_error"] <= ATTN and g["attn_full_error"] <= ATTN


def test_a_long_and_a_short_request_share_one_engine(params, weights):
    """A 100-token prompt and an 11-token one in one queue, decoding side by
    side: both are the reference's, the rings' bytes never change, and a
    window layer's decode row reads at most the window."""
    engine = PagedEngine(CFG, params, ECFG)
    before = engine.stats()
    long, short = prompt(11, 100), prompt(12, 11)
    outs = serve(engine, [long, short], [20, 24], together=True)
    for p, out in zip((long, short), outs):
        g = gaps(weights, p, out)
        assert max(g["gaps"]) <= GAP and g["argmax_equal"] == len(out["token_ids"])
    stats = engine.stats()
    assert stats["window_bytes"] == before["window_bytes"] == 2 * 6 * 3 * 40 * 2 * 16 * 4
    assert stats["kv_bytes"] == before["kv_bytes"]
    # a decode row a step a slot, six window layers: never more than the
    # window, and less only while a sequence is shorter than it
    rows = stats["tokens_out"] - 2            # the first tokens are chunks'
    assert 0 < stats["window_positions"] <= 6 * 8 * rows
    short_rows = sum(min(11 + i + 1, 8) for i in range(23))
    assert stats["window_positions"] == 6 * (8 * 19 + short_rows)
    # had the layers been full, the same rows would have read these
    assert stats["attn_positions_live"] == sum(
        100 + i + 1 for i in range(19)) + sum(11 + i + 1 for i in range(23))
    assert stats["kv_positions_live"] == 2 * stats["attn_positions_live"]
    assert stats["free_blocks"] == ECFG.num_kv_blocks
    assert stats["prefill_chunks"] == 4 + 1


def test_a_ladder_with_slot_state_and_no_snapshots(params):
    """The combination no other family has: prompts in chunks (`chunk_at`
    of three), something a slot carries beside its blocks, and no snapshot
    of it: the prefix cache is refused in the family's words, off by
    default, and transferred KV is refused too."""
    steps = step_set(CFG)
    assert steps is _mellum_steps
    assert steps.chunk_ladder(ECFG) and steps.SLOT_STATE == "wk"
    assert steps.SNAPSHOT_STATE is None and steps.SNAPSHOT_POLICY is None
    assert not hasattr(steps, "make_prefill")
    engine = PagedEngine(CFG, params, ECFG)
    assert engine._chunk_at(None, 0, 0)[0].shape == (3,)
    assert engine._prefix_cache is None
    with pytest.raises(ValueError, match="do not resume a window layer"):
        PagedEngine(CFG, params, EngineConfig(
            max_num_seqs=3, kv_block_size=4, num_kv_blocks=96,
            max_model_len=128, prefix_cache=True))
    with pytest.raises(ValueError, match="window layers"):
        steps.make_kv_inject(CFG, ECFG)
    with pytest.raises(ValueError, match="check_routing"):
        engine.check_prefill(prompt(0, 9))
    assert _mellum_steps._ring_positions(CFG, ECFG) == 40
    assert _mellum_steps._ring_positions(
        mellum.MellumConfig(), EngineConfig(
            max_num_seqs=48, kv_block_size=32, max_model_len=33792)) == 1280


def test_the_family_is_served_by_its_name():
    from ray_tpu.llm import LLMConfig

    cfg, params = LLMConfig(model="mellum:tiny", seed=3).build_model()
    assert type(cfg) is mellum.MellumConfig and cfg == CFG
    assert len(params["layers"]) == 8
    big = mellum.MellumConfig.mellum2_12b(n_layers=8, layer_ids=tuple(range(8)))
    n = sum(x.size for x in jax.tree.leaves(jax.eval_shape(
        lambda: mellum.init_params(big, jax.random.PRNGKey(0)))))
    assert n == 3_794_968_832          # 3.342B + 0.453B: the issue's 3.795B

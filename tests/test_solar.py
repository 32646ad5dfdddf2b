"""The Solar-Open2 family (models/solar.py, llm/_solar_steps.py, the engine's
state snapshots and the prefix cache that keeps them) against the plain
float32 reference (benchmark/lib/reference_solar.py), at a tiny size on the
CPU: hidden 64, GQA 8 heads on 2 KV heads of 16, KDA 4 heads of 16, 16
experts top-2, 4 layers = GQA, KDA, KDA, KDA. Chunks of 32 and 64 rows.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ling as rl
from benchmark.lib import reference_solar as ref
from benchmark.runners._inside_solar import ProgramWeightsSolar
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.llm import _solar_steps
from ray_tpu.llm._prefix_cache import (
    PrefixCache, SnapshotPolicy, SnapshotsAtChunks, chain_keys)
from ray_tpu.models import ling, solar
from ray_tpu.ops import grouped_ffn

HP = dict(hidden_size=64, num_attention_heads=8, num_key_value_heads=2,
          head_dim=16, linear_attn_config=dict(
              short_conv_kernel_size=4, head_dim=16, num_heads=4,
              num_kv_heads=None),
          rms_norm_eps=1e-5, gqa_interval=3, kda_allow_neg_eigval=True,
          n_routed_experts=16, num_experts_per_tok=2,
          routed_scaling_factor=1, num_hidden_layers=4, vocab_size=512)
CFG = solar.SolarConfig.tiny()
ECFG = EngineConfig(max_num_seqs=3, kv_block_size=16, num_kv_blocks=64,
                    max_model_len=256, prefix_cache=True,
                    num_state_snapshots=6)
SPEC = ref.spec_of(HP)


@pytest.fixture(scope="module")
def params():
    return solar.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return ProgramWeightsSolar(params, 2 * 16)


@pytest.fixture()
def engine(params):
    return PagedEngine(CFG, params, ECFG)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(0, 512, n)]


def serve(engine, prompts, max_tokens=8, **kw):
    """The prompts through the engine's loop, one after the other."""
    async def go():
        engine._pending = engine._loop_task = None
        return [await engine.check_routing(p, max_tokens, **kw)
                for p in prompts]

    return asyncio.run(go())


def gaps(weights, p, out, routing=None):
    return ref.teacher_forced_gaps(
        HP, weights, p, out["token_ids"],
        out["routing"] if routing is None else routing, 64)


# --- the blocks against the reference ----------------------------------------


def test_the_layer_kinds_are_the_published_ones():
    assert CFG.kinds() == ref.layer_kinds(HP) == ["gqa", "kda", "kda", "kda"]
    cell = solar.SolarConfig(n_layers=4, layer_ids=(4, 5, 6, 7))
    assert cell.kinds() == ["gqa", "kda", "kda", "kda"]
    assert solar.SolarConfig().kinds()[:5] == ["gqa", "kda", "kda", "kda", "gqa"]


@pytest.mark.parametrize("layer,kind", [(0, "gqa"), (1, "kda"), (3, "kda")])
def test_an_attention_block_equals_the_reference(params, weights, layer, kind):
    x = jax.random.normal(jax.random.PRNGKey(layer), (128, 64))
    got = solar.attention(CFG, params["layers"][layer], x,
                          jnp.arange(128) < 100)
    block = ref.gqa if kind == "gqa" else ref.kda
    want = block(SPEC, x[:100], weights.layer(layer))
    np.testing.assert_allclose(got[:100], want, atol=2e-5)


def test_the_gates_are_the_stated_ones(params, weights):
    """beta reaches past 1 (negative eigenvalues), the decay is the low-rank
    softplus gate and has no lower bound, the GQA gate is element-wise."""
    p, w = params["layers"][1], weights.layer(1)
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (64, 64))
    _, _, _, g, beta = ref.kda_inputs(SPEC, x, w)
    assert 1.0 < float(beta.max()) < 2.0 and float(beta.min()) > 0.0
    assert float(g.max()) < 0.0
    qkv = jnp.zeros((64, 3 * 4 * 16))
    _, _, _, g2, beta2 = solar.kda_inputs(CFG, p, x, qkv)
    np.testing.assert_allclose(g2, g, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(beta2, beta, atol=1e-6)
    assert p["wa_down"].shape == (64, 8) and p["wg_up"].shape == (8, 64)
    assert params["layers"][0]["wg"].shape == (64, 8 * 16)


def test_forward_equals_the_reference(params, weights):
    toks = prompt(1, 200)
    padded = np.zeros(256, np.int32)
    padded[:200] = toks
    got = jax.jit(lambda p, t: solar.forward(CFG, p, t, 200))(
        params, jnp.asarray(padded))
    want = ref.logits_at(HP, weights, toks, list(range(200)))
    np.testing.assert_allclose(np.asarray(got)[:200], want, atol=2e-4)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_routing_without_groups_equals_the_reference(params, seed):
    p = params["layers"][seed]
    x = jax.random.normal(jax.random.PRNGKey(seed), (40, 64))
    experts, weights_, bits, _ = ling.route(CFG, p, x)
    want_e, want_w, kept = rl.route(SPEC, x, p["router"], p["router_bias"])
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want_e, -1))
    np.testing.assert_allclose(np.sort(weights_, -1), np.sort(want_w, -1),
                               atol=1e-6)
    assert bool(kept.all()) and set(np.asarray(bits).tolist()) == {1}
    np.testing.assert_allclose(weights_.sum(-1), 1.0, atol=1e-6)


def test_the_eight_shares_add_up(params, weights):
    """Held 0-1, 2-3, ... 14-15, the shared expert counted once = the whole
    layer of the uncut reference."""
    p = params["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(7), (48, 64))
    live = jnp.ones((48,), bool)
    total, held_pairs = jnp.zeros_like(x), 0
    for share in range(8):
        cfg = dataclasses.replace(CFG, held_start=2 * share, n_held=2)
        mine = {**p, **{k: p[k][2 * share:2 * share + 2]
                        for k in ("e_w1", "e_w3", "e_w2")}}
        y, _, counters, _ = ling.moe_held(cfg, mine, x, live,
                                          shared=share == 0)
        total = total + y
        held_pairs += int(counters[1])
    assert held_pairs == 48 * 2            # every pair lands on one share
    want, _, _ = rl.moe(SPEC._replace(held=16), x, weights.layer(2),
                        lambda lo, hi: weights.experts(2, lo, hi))
    np.testing.assert_allclose(total, want, atol=1e-5)


@pytest.mark.parametrize("path", [grouped_ffn.XLA, grouped_ffn.KERNEL])
def test_a_chunk_steps_rows_take_the_grouped_matmul_as_it_stands(
        params, weights, monkeypatch, path):
    """600 rows, more than any step of Ling's carries: the layer is the
    reference's through the grouped SwiGLU's twin, which is what runs off the
    TPU, and through its kernel (in the interpreter), whose rows past the
    held ones come back undefined and are masked by `moe_held`."""
    monkeypatch.setattr(grouped_ffn, "_INTERPRET", path == grouped_ffn.KERNEL)
    p = params["layers"][1]
    assert grouped_ffn.ffn_path(p["e_w1"]) == path
    x = jax.random.normal(jax.random.PRNGKey(3), (600, 64))
    y = ling.moe_held(CFG, p, x, jnp.ones((600,), bool))[0]
    want, _, _ = rl.moe(SPEC._replace(held=16), x, weights.layer(1),
                        lambda lo, hi: weights.experts(1, lo, hi))
    np.testing.assert_allclose(y, want, atol=1e-5)


def test_the_expert_bias_is_balanced_through_this_familys_layers():
    raw = solar.init_params(CFG, jax.random.split(jax.random.PRNGKey(3))[0])
    bal = jax.jit(lambda k: solar.seeded_params(CFG, k))(jax.random.PRNGKey(3))
    np.testing.assert_array_equal(raw["layers"][1]["router"],
                                  bal["layers"][1]["router"])
    assert not np.array_equal(raw["layers"][1]["router_bias"],
                              bal["layers"][1]["router_bias"])


# --- chunks, hand-over, snapshots ----------------------------------------------


def test_a_prompt_in_chunks_equals_the_whole_prompt_equals_the_reference(
        engine, params, weights):
    """200 tokens = chunks of 64, 64, 64 and 8 (in a 32-row step) through the
    decode step, the state and the tail handed from chunk to chunk and to the
    decode rows; the logits are the whole-prompt forward pass's and the
    reference's."""
    p = prompt(11, 200)
    out, = serve(engine, [p])
    assert engine.stats()["prefill_chunks"] == 4
    assert out["routing"].shape == (4, 200 + 7, 3)
    g = gaps(weights, p, out)
    assert g["argmax_equal"] == 8 and max(g["gaps"]) < 2e-4
    assert g["routing"]["expert_steps"] == 0.0
    # prompts run as chunks: no whole-prompt program is built, checked or
    # lowered, and the refusal names the family's check
    assert engine._prefill is None
    for refused in (engine.check_prefill, lambda p: engine.step_hlo([len(p)])):
        with pytest.raises(ValueError, match="no whole-prompt.*check_routing"):
            refused(p)
    padded = np.zeros(256, np.int32)
    padded[:200] = p
    whole = solar.forward(CFG, params, jnp.asarray(padded), 200)[199]
    assert int(jnp.argmax(whole)) == out["token_ids"][0]


def test_a_request_resumed_from_a_snapshot_equals_a_run_from_position_0(
        engine, weights):
    doc = prompt(21, 150)
    q1, q2 = doc + prompt(22, 21), doc + prompt(23, 33)
    first, = serve(engine, [q1])
    s = engine.stats()
    assert first["resume_from"] == 0 and s["snapshots_taken"] == 2
    warm, = serve(engine, [q2], mechanisms=True)
    s = engine.stats()
    # 144 tokens of the document matched; the deepest snapshot is at 128
    assert warm["resume_from"] == 128 and s["snapshots_restored"] == 1
    assert s["prefix_cache"]["block_hits"] == 9
    assert s["snapshot_rerun_tokens"] == 144 - 128
    cold, = serve(engine, [q2], cold=True)
    assert cold["resume_from"] == 0
    assert warm["token_ids"] == cold["token_ids"]
    np.testing.assert_array_equal(warm["routing"], cold["routing"][:, 128:])
    # the reference from position 0, the shared rows' routing the first's
    routing = np.concatenate([first["routing"][:, :128], warm["routing"]], 1)
    g = gaps(weights, q2, warm, routing)
    assert g["argmax_equal"] == 8 and max(g["gaps"]) < 2e-4
    # the slot's state: the snapshot, the question's chunk, seven decode steps
    got = ref.mechanism_readings(warm, weights.routers())
    assert got["chunk_rows"] == 183 - 128 and got["state_steps"] == 55 + 7
    assert got["state_error"] < 1e-5 < 1e-3 < got["state_error_bf16"]
    assert got["router_f32_steps"] < 1.0 < 100 < got["router_f32_steps_bf16"]
    assert engine._probe_slot is None


def test_a_precision_lower_fails_the_tolerances(engine, weights):
    """The reference itself with its activations in float8, judged as the
    program is, lies outside the logit limit; a state rounded to bf16 and a
    bf16 router lie outside theirs (the test above)."""
    p = prompt(31, 120)
    out, = serve(engine, [p])
    ok = gaps(weights, p, out)
    low = ref.teacher_forced_gaps(HP, weights, p, out["token_ids"],
                                  out["routing"], 64,
                                  activations=jnp.float8_e4m3fn)
    step = 2.0 ** -8 * max(1.0, ok["max_abs_logit"])
    assert max(ok["gaps"]) / step < 1.0 < 8.0 < max(low["gaps"]) / max(
        step, 2.0 ** -8 * low["max_abs_logit"])


def test_a_dropped_hand_over_shows_in_the_logits(engine, weights, monkeypatch):
    """Planted: the chunks start from zeros, not from what the chunk before
    left. The reference from position 0 does not agree."""
    from ray_tpu.ops import kda as kda_ops

    real = kda_ops.kda_chunked
    monkeypatch.setattr(kda_ops, "kda_chunked", lambda q, k, v, g, beta, s, **kw:
                        real(q, k, v, g, beta, jnp.zeros_like(s), **kw))
    broken = PagedEngine(CFG, engine.params, ECFG)
    p = prompt(41, 200)
    out, = serve(broken, [p])
    g = gaps(weights, p, out)
    assert max(g["gaps"]) > 1e-2


def test_requests_in_one_batch_do_not_touch_each_other(engine):
    a, b = prompt(51, 200), prompt(52, 90)

    async def together():
        engine._pending = engine._loop_task = None
        return await asyncio.gather(engine.check_routing(a, 8, cold=True),
                                    engine.check_routing(b, 8, cold=True))

    both = asyncio.run(together())
    alone_a, = serve(engine, [a], cold=True)
    alone_b, = serve(engine, [b], cold=True)
    assert both[0]["token_ids"] == alone_a["token_ids"]
    assert both[1]["token_ids"] == alone_b["token_ids"]


# --- the prefix cache on this family ---------------------------------------------
# --- the prefix cache on this family ---------------------------------------------


def test_a_request_admitted_mid_prompt_keeps_the_snapshot_it_starts_from(
        params):
    """B shares A's first blocks and is admitted while A is still in chunks,
    with A's first snapshot pinned. B has more of its prompt left than A, so
    A is both the oldest and the one with the least left and B waits behind
    it (a B with less left would run its first chunk between A's, and copy
    the snapshot in at once). A goes on to take three more and recycles its
    oldest, which is B's: the entry must not be handed out and overwritten
    before B's first chunk has copied it in (422 tokens on, B's answer no
    longer shows the state it started from: the entry itself is read)."""
    ecfg = dataclasses.replace(ECFG, max_model_len=640)
    engine = PagedEngine(CFG, params, ecfg)
    a = prompt(55, 540)
    b = a[:140] + prompt(56, 410)
    inner, copied_in = engine._chunk_at, []

    def chunk_at(req, at, n):
        if req is not None and req.restore >= 0:
            copied_in.append(np.asarray(engine.snap_state[req.restore]))
        return inner(req, at, n)

    engine._chunk_at = chunk_at

    async def go():
        engine._pending = engine._loop_task = None
        first = asyncio.ensure_future(engine.check_routing(a, 8))
        while engine.stats()["snapshots_taken"] < 1:
            await asyncio.sleep(0)
        assert engine._prefilling[0].cursor == 128
        second = await engine.check_routing(b, 8, mechanisms=True)
        return await first, second

    out_a, out_b = asyncio.run(go())
    assert out_b["resume_from"] == 128
    # what B's first chunk started from is what the entry held at admission
    assert len(copied_in) == 1 and copied_in[0].any()
    assert np.array_equal(copied_in[0], out_b["state0"])
    stats = engine.stats()
    # A's four, then B's three: B never ran a chunk between A's
    assert stats["snapshots_taken"] == 7 and stats["chunk_overtakes"] == 0
    assert stats["snapshots_restored"] == 1
    fresh = PagedEngine(CFG, params, ecfg)
    assert out_b["token_ids"] == serve(fresh, [b], cold=True)[0]["token_ids"]
    assert out_a["token_ids"] == serve(fresh, [a], cold=True)[0]["token_ids"]


def test_the_second_question_hits_and_an_evicted_snapshot_shortens_the_match(
        params, weights):
    """A pool of 20 blocks: the second question resumes from a snapshot;
    then another document evicts the first's last blocks, and the snapshot
    at their end goes with them: the third question finds a shorter match,
    resumes from a shallower snapshot or from position 0, and is still
    right."""
    engine = PagedEngine(CFG, params, dataclasses.replace(
        ECFG, num_kv_blocks=20))
    doc = prompt(61, 150)
    q = [doc + prompt(62 + i, 20 + i) for i in range(3)]
    serve(engine, q[:1])
    second, = serve(engine, q[1:2])
    assert second["resume_from"] == 128
    s = engine.stats()
    assert s["prefix_cache"]["snapshots"] >= 2 and s["snapshots_evicted"] == 0
    other, = serve(engine, [prompt(71, 230)])
    s = engine.stats()
    assert s["prefix_cache"]["evictions"] > 0 and s["snapshots_evicted"] >= 1
    third, = serve(engine, q[2:])
    assert third["resume_from"] < 128
    g = gaps(weights, q[2], third) if third["resume_from"] == 0 else None
    fresh, = serve(PagedEngine(CFG, params, ECFG), q[2:])
    assert third["token_ids"] == fresh["token_ids"]
    assert g is None or max(g["gaps"]) < 2e-4


def test_a_displaced_snapshot_shortens_the_match_too(params):
    """Two snapshots in the pool: a second document's take the first's
    entries (least recently used), and the first document's next question
    runs from position 0, blocks matched and all, to the same answer."""
    engine = PagedEngine(CFG, params, dataclasses.replace(
        ECFG, num_state_snapshots=2))
    doc = prompt(81, 150)
    q1, q2 = doc + prompt(82, 20), doc + prompt(83, 25)
    serve(engine, [q1])
    serve(engine, [prompt(84, 150)])
    s = engine.stats()
    assert s["snapshots_taken"] == 4 and s["snapshots_evicted"] == 2
    again, = serve(engine, [q2])
    assert again["resume_from"] == 0
    assert engine.stats()["snapshot_rerun_tokens"] == 144
    fresh, = serve(PagedEngine(CFG, params, ECFG), [q2])
    assert again["token_ids"] == fresh["token_ids"]


def test_a_trimmed_tail_falls_back_to_the_far_snapshot(params, monkeypatch):
    """Chunks of 128 and a far snapshot every 3 of them: a 700-token document
    leaves snapshots at 384 (far), 512 and 640. Eviction trims the idle
    document's tail to 432 tokens and the two deep snapshots go with their
    blocks: the next question resumes at 384, not at 0, to the same answer."""
    monkeypatch.setattr(SnapshotsAtChunks, "SNAPSHOT_FAR", 3)
    ecfg = dataclasses.replace(ECFG, max_model_len=768)
    engine = PagedEngine(CFG, params, ecfg)
    doc = prompt(91, 700)
    q1, q2 = doc + prompt(92, 20), doc + prompt(93, 25)
    serve(engine, [q1])
    cache = engine._prefix_cache
    keys = chain_keys(q1, 16)
    assert [i * 16 for i, k in enumerate(keys, 1) if cache.has_snapshot(k)] \
        == [384, 512, 640]
    engine.free_blocks.extend(cache.evict(45 - 27))
    assert engine.stats()["snapshots_evicted"] == 2
    again, = serve(engine, [q2])
    assert again["resume_from"] == 384
    assert engine.stats()["snapshot_rerun_tokens"] == 432 - 384
    fresh, = serve(PagedEngine(CFG, params, ecfg), [q2], cold=True)
    assert again["token_ids"] == fresh["token_ids"]


class SnapshotsAtBlocks(SnapshotPolicy):
    """A third policy, written here: a snapshot wherever a chunk ends on a
    block boundary of the prompt's full blocks, and all of them kept."""

    def resume(self, keys, n_blocks):
        covered, restore = self.cache.deepest_snapshot(keys, n_blocks)
        return covered * self.bs, restore, 0

    def here(self, req, end):
        return end % self.bs == 0 and end // self.bs <= len(req.block_keys)


def test_a_policy_written_elsewhere_is_served_by_the_engine_as_it_stands(
        params, monkeypatch):
    """The seam: the step set names another policy and `PagedEngine`, not
    edited, serves by it. A document of 200 tokens leaves snapshots at 64,
    128 and 192 and keeps all three (the family's own keeps two); a prompt
    that parts from it at 100 resumes at 64, and its chunk of 32 tokens ends
    on block 10 (160 tokens, no multiple of the widest chunk) and leaves
    one there; a second question resumes at 192 with nothing to run again,
    to a cold run's tokens."""
    monkeypatch.setattr(_solar_steps, "SNAPSHOT_POLICY", SnapshotsAtBlocks)
    engine = PagedEngine(CFG, params, ECFG)
    assert type(engine._snapshots) is SnapshotsAtBlocks
    doc = prompt(131, 200)
    q1, q2 = doc + prompt(132, 21), doc + prompt(133, 30)
    fork = doc[:100] + prompt(134, 60)
    cache = engine._prefix_cache

    def snapshots_of(p):
        return [i * 16 for i, k in enumerate(chain_keys(p, 16), 1)
                if cache.has_snapshot(k)]

    serve(engine, [q1])
    assert snapshots_of(q1) == [64, 128, 192]
    forked, = serve(engine, [fork])
    assert forked["resume_from"] == 64
    assert snapshots_of(fork) == [64, 128, 160]
    warm, = serve(engine, [q2])
    s = engine.stats()
    assert warm["resume_from"] == 192 and s["snapshots_restored"] == 2
    assert s["snapshots_taken"] == 5 and s["snapshots_evicted"] == 0
    assert s["snapshot_rerun_tokens"] == 96 - 64
    cold, = serve(engine, [q2], cold=True)
    assert cold["resume_from"] == 0
    assert warm["token_ids"] == cold["token_ids"]


def test_a_new_document_takes_the_oldest_idle_one_whole(params):
    """A pool of 22 blocks holds two documents of 10 cached blocks. The
    third needs 12 and takes them from the first, the least recently used,
    which goes whole; the second keeps its tail and its deep snapshot (a
    leaf off every idle chain a round would cost it five blocks and the
    snapshot at 128). A second question on the second and on the third then
    resumes at 128 as on an untouched document, to a fresh engine's answer."""
    ecfg = dataclasses.replace(ECFG, num_kv_blocks=22)
    engine = PagedEngine(CFG, params, ecfg)
    docs = [prompt(100 + i, 150) for i in range(3)]
    first = [d + prompt(110 + i, 21) for i, d in enumerate(docs)]
    again = [d + prompt(120 + i, 33) for i, d in enumerate(docs)]
    serve(engine, first)
    cache = engine._prefix_cache
    assert not any(k in cache._entries for k in chain_keys(first[0], 16))
    for p in first[1:]:
        held = cache.match(chain_keys(p, 16))
        assert len(held) == 10
        cache.cancel_match(held)
    s = engine.stats()
    assert s["prefix_cache"]["evictions"] == 10 and s["admissions"] == 3
    hits = s["prefix_cache"]["block_hits"]
    warm = serve(engine, again[1:])
    assert [w["resume_from"] for w in warm] == [128, 128]
    s = engine.stats()
    assert s["snapshots_restored"] == 2
    assert s["snapshot_rerun_tokens"] == 2 * (144 - 128)
    assert s["prefix_cache"]["block_hits"] - hits == 2 * 9
    assert s["admissions"] == 5 and s["admit_host_s"] > 0
    assert (s["prefix_cache"]["evict_examined"]
            <= s["prefix_cache"]["evictions"] + s["prefix_cache"]["evict_calls"])
    cold = serve(PagedEngine(CFG, params, ecfg), again[1:], cold=True)
    assert [w["token_ids"] for w in warm] == [c["token_ids"] for c in cold]


def test_the_prefix_cache_needs_a_snapshot_pool_and_ling_still_refuses(params):
    with pytest.raises(ValueError, match="num_state_snapshots"):
        PagedEngine(CFG, params, dataclasses.replace(
            ECFG, num_state_snapshots=0))
    off = PagedEngine(CFG, params, dataclasses.replace(
        ECFG, prefix_cache=None, num_state_snapshots=0))
    assert off.stats()["prefix_cache"] is None
    out, = serve(off, [prompt(91, 100)])
    assert len(out["token_ids"]) == 8
    cfg = ling.LingConfig.tiny()
    with pytest.raises(ValueError, match="recurrent"):
        PagedEngine(cfg, ling.init_params(cfg, jax.random.PRNGKey(0)),
                    EngineConfig(max_num_seqs=2, num_kv_blocks=32,
                                 prefix_cache=True, num_state_snapshots=4))


def test_stats_count_snapshots_chunks_and_experts(engine):
    serve(engine, [prompt(95, 150)])
    s = engine.stats()
    for key in ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
                "moe_load_max", "state_bytes", "snapshot_bytes",
                "kv_positions_live", "snapshots_taken", "snapshots_restored",
                "snapshots_evicted", "snapshot_rerun_tokens",
                "steps_with_chunk", "prefill_chunk_tokens",
                "prefill_chunk_pad_tokens", "chunk_positions_live",
                "chunk_attn_pairs"):
        assert key in s, key
    assert s["state_bytes"] == engine.state.nbytes + engine.tails.nbytes
    assert s["snapshot_bytes"] == (engine.snap_state.nbytes
                                   + engine.snap_tails.nbytes)
    assert engine.snap_state.shape == (7, 3, 4, 16, 16)
    assert s["prefill_chunk_tokens"] == 150 and s["steps_with_chunk"] == 3
    assert s["chunk_positions_live"] == 64 + 128 + 150
    assert s["chunk_attn_pairs"] == 150 * 151 // 2
    # every row routed top-2 in each of the 4 layers
    assert s["moe_pairs_routed"] == (150 + 7) * 2 * 4
    assert s["kv_positions_live"] == s["attn_positions_live"] * 1


def test_llm_config_resolves_the_family():
    from ray_tpu.llm import LLMConfig, step_set

    cfg, params = LLMConfig(model="solar:tiny").build_model()
    assert isinstance(cfg, solar.SolarConfig)
    assert step_set(cfg).SNAPSHOT_STATE == "snap_state"
    assert params["layers"][0]["wqkv"].shape == (64, (8 + 4) * 16)


# --- the cache's own bookkeeping ---------------------------------------------------


def test_prefix_cache_snapshots_live_and_die_with_their_blocks():
    cache = PrefixCache(4, num_snapshots=2)
    keys = chain_keys(list(range(16)), 4)
    cache.register(keys, [1, 2, 3, 4])
    a = cache.reserve_snapshot()
    assert cache.attach_snapshot(keys[1], a) and cache.has_snapshot(keys[1])
    # a block that is not cached, or has one already, takes none
    assert not cache.attach_snapshot(b"none", cache.reserve_snapshot())
    assert not cache.attach_snapshot(keys[1], cache.reserve_snapshot())
    b = cache.reserve_snapshot()
    assert cache.attach_snapshot(keys[3], b) and {a, b} == {0, 1}
    assert cache.deepest_snapshot(keys, 4) == (4, b)
    assert cache.deepest_snapshot(keys, 3) == (2, a)
    assert cache.deepest_snapshot(keys, 1) == (0, -1)
    # a match uses every snapshot on its run, not the deepest alone
    use = {k: cache._entries[k].snap_use for k in (keys[1], keys[3])}
    assert cache.deepest_snapshot(keys, 4) == (4, b)
    assert all(cache._entries[k].snap_use > use[k] for k in use)
    # the pool is full: the least recently used gives way, a pinned one not
    cache.pin_snapshot(b)
    assert cache.deepest_snapshot(keys, 3) == (2, a)      # a used last
    assert cache.reserve_snapshot() == a and not cache.has_snapshot(keys[1])
    cache.attach_snapshot(keys[0], a)
    cache.pin_snapshot(a)
    assert cache.reserve_snapshot() == -1
    # nor does a request recycling its own free one another is to start from
    cache.drop_snapshot(keys[0])
    assert cache.has_snapshot(keys[0]) and cache.reserve_snapshot() == -1
    cache.pin_snapshot(a, False)
    cache.pin_snapshot(b, False)
    # eviction is leaf-first: block 4's snapshot goes with it
    for blk in (1, 2, 3, 4):
        cache.decref_block(blk)
    assert cache.evictable_blocks() == 4 == sum(
        e.refs == 0 for e in cache._entries.values())
    assert cache.evict(1) == [4] and cache.stats()["snapshots"] == 1
    assert cache.evictable_blocks() == 3
    assert cache.deepest_snapshot(keys, 3) == (1, a)
    assert cache.snapshots_taken == 3 and cache.snapshots_evicted == 2
    cache.clear()
    assert cache.stats()["snapshots"] == 0 and cache.reserve_snapshot() in (0, 1)

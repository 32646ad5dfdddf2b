"""The Brumby family (models/brumby.py, llm/_brumby_steps.py,
ops/power_retention.py, the engine's snapshot policy for a family whose only
memory is a state) against the plain float32 reference
(benchmark/lib/reference_brumby.py: the quadratic form), at a tiny size on
the CPU: hidden 64, 4 query heads on 2 KV heads of 16, 2 layers. Chunks of 32
and 64 rows. The tests named `test_assumed_*` each hold one point of the
configuration file's `assumed`.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_brumby as ref
from benchmark.runners._inside_brumby import ProgramWeightsBrumby, unpack
from ray_tpu.llm import LLMConfig, _brumby_steps, step_set
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.llm._prefix_cache import SnapshotsAtMatch
from ray_tpu.models import brumby
from ray_tpu.ops import power_retention as pr

HP = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
          head_dim=16, intermediate_size=128, rope_theta=1000000,
          rms_norm_eps=1e-6, retention_eps=1e-6, num_hidden_layers=2,
          vocab_size=512)
CFG = brumby.BrumbyConfig.tiny()
ECFG = EngineConfig(max_num_seqs=3, kv_block_size=16, num_kv_blocks=128,
                    max_model_len=256, prefix_cache=True,
                    num_state_snapshots=4)
SPEC = ref.spec_of(HP)
EPS = 1e-6


@pytest.fixture(scope="module")
def params():
    return brumby.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def weights(params):
    return ProgramWeightsBrumby(params, 2 * 16)


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


def gaps(weights, p, out, **kw):
    return ref.teacher_forced_gaps(HP, weights, p, out["token_ids"], 128, **kw)


def layer_of(params, i):
    return {k: v[i] for k, v in params["layers"].items()}


def rows(seed, T, KV=2, G=2, d=16, gate=(0.0, 1.0)):
    """Seeded q (scaled), k, v [T, ...] and log gates with sigmoid in
    `gate`."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(T, KV, G, d)), jnp.float32) * d ** -0.5
    k = jnp.asarray(rng.normal(size=(T, KV, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, KV, d)), jnp.float32)
    gamma = jnp.log(jnp.asarray(rng.uniform(*gate, size=(T, KV)), jnp.float32))
    return q, k, v, gamma


# --- the operation -------------------------------------------------------------


@pytest.mark.parametrize("d", [8, 16, 128])
def test_assumed_power_two_phi_inner_product_is_the_squared_score(d):
    rng = np.random.default_rng(d)
    x, y = (jnp.asarray(rng.normal(size=(5, d)), jnp.float32) for _ in "xy")
    got = np.sum(np.asarray(pr.phi(x), np.float64)
                 * np.asarray(pr.phi(y), np.float64), axis=-1)
    want = np.sum(np.asarray(x, np.float64) * np.asarray(y, np.float64), -1) ** 2
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=2e-4)
    assert pr.phi(x).shape[-1] == pr.phi_width(d) >= d * (d + 1) // 2
    assert pr.phi_width(128) == 8704 <= 16384


@pytest.mark.parametrize("gate", [(0.001, 0.05), (0.95, 0.9999), (0.0, 1.0)])
def test_steps_repeated_equal_the_chunks_equal_the_quadratic_form(gate):
    """Gates near 0 (a head that forgets at once), near 1 (one that keeps
    everything) and spread: every exponent is a difference <= 0."""
    T, W = 70, pr.phi_width(16)
    q, k, v, gamma = rows(3, T, gate=gate)
    want = pr.retention_quadratic(q, k, v, gamma, EPS)
    S, Z, out = jnp.zeros((1, 2, W, 16)), jnp.zeros((1, 2, 16, 16)), []
    for t in range(T):
        o, S, Z = pr.retention_step_xla(
            q[t][None], k[t][None], v[t][None], jnp.exp(gamma[t])[None], S, Z,
            EPS)
        out.append(o[0])
    # where a head forgets at once a row's weights can sum to little more
    # than eps: the outputs are held looser there, the states as tightly
    tol = dict(rtol=2e-3, atol=2e-3) if gate[1] < 0.5 else dict(
        rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(jnp.stack(out), want, **tol)
    Sc, Zc, out = jnp.zeros((2, W, 16)), jnp.zeros((2, 16, 16)), []
    for lo in range(0, T, 32):
        o, Sc, Zc = pr.retention_chunked(
            q[lo:lo + 32], k[lo:lo + 32], v[lo:lo + 32], gamma[lo:lo + 32],
            Sc, Zc, EPS)
        out.append(o)
    np.testing.assert_allclose(jnp.concatenate(out), want, **tol)
    np.testing.assert_allclose(Sc, S[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(Zc, Z[0], rtol=1e-4, atol=1e-5)
    # the state is the direct sum the reference gives, by the layout's own
    # unpacking
    direct = ref.direct_state(k, v, gamma)
    assert ref.relative_error(pr.unpack_state(Sc), direct) < 1e-5


def test_the_pallas_kernel_in_the_interpreter_equals_its_xla_twin(monkeypatch):
    """At the published head width: 3 rows on 2 KV heads, a group of five,
    layer 1 of 2, the middle row pointed at the spare slot."""
    monkeypatch.setattr(pr, "_INTERPRET", True)
    assert pr.step_path() == pr.KERNEL
    B, KV, G, d, L = 3, 2, 5, 128, 2
    W = pr.phi_width(d)
    rng = np.random.default_rng(0)
    q, k, v, _ = rows(1, B, KV, G, d)
    gate = jnp.asarray(rng.uniform(0.5, 1, size=(B, KV)), jnp.float32)
    S = jnp.asarray(rng.normal(size=(L, B + 1, KV, W, d)), jnp.float32)
    Z = jnp.asarray(rng.normal(size=(L, B + 1, KV, d, d)), jnp.float32)
    slots = np.asarray([0, B, 2])
    o, S1, Z1 = jax.jit(lambda *a: pr.retention_step(*a, EPS))(
        q, k, v, gate, S, Z, jnp.int32(1), jnp.asarray(slots, jnp.int32))
    ox, Sx, Zx = pr.retention_step_xla(q, k, v, gate, S[1][slots], Z[1][slots],
                                       EPS)
    np.testing.assert_allclose(o, ox, rtol=2e-4, atol=2e-3)
    np.testing.assert_allclose(S1[1][slots], Sx, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(Z1[1][slots], Zx, rtol=1e-5, atol=1e-5)
    # in place: the other layer and the slot no row named are as they were
    np.testing.assert_array_equal(S1[0], S[0])
    np.testing.assert_array_equal(S1[1, 1], S[1, 1])
    np.testing.assert_array_equal(Z1[1, 1], Z[1, 1])


def test_assumed_one_gate_and_one_state_a_kv_head_shared_by_its_group():
    """A group's queries read one state: the state after a row does not
    depend on q, each query head's output is what it alone would read, and
    the gate has one value a KV head."""
    q, k, v, gamma = rows(5, 1, KV=2, G=5)
    W = pr.phi_width(16)
    S0 = jnp.asarray(np.random.default_rng(1).normal(size=(1, 2, W, 16)),
                     jnp.float32)
    Z0 = jnp.einsum("bkwa,bkwc->bkac", S0[..., :16, :], S0[..., :16, :])
    o, S, Z = pr.retention_step_xla(q, k, v, jnp.exp(gamma), S0, Z0, EPS)
    assert S.shape == (1, 2, W, 16) and o.shape == (1, 2, 5, 16)
    for h in range(5):
        oh, Sh, Zh = pr.retention_step_xla(
            q[:, :, h:h + 1], k, v, jnp.exp(gamma), S0, Z0, EPS)
        np.testing.assert_allclose(oh[:, :, 0], o[:, :, h], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_array_equal(Sh, S)
    assert CFG.group == 2 and brumby.BrumbyConfig().group == 5
    p = brumby.init_params(brumby.BrumbyConfig.tiny(), jax.random.PRNGKey(1))
    assert p["layers"]["wg"].shape == (2, 64, 2)          # [layers, D, KV]
    assert p["layers"]["bg"].shape == (2, 2)


# --- the blocks against the reference ------------------------------------------


@pytest.mark.parametrize("layer", [0, 1])
def test_a_retention_layer_equals_the_references_quadratic_form(
        params, weights, layer):
    x = jax.random.normal(jax.random.PRNGKey(layer), (128, 64), jnp.float32)
    got = brumby.ret_sequence(CFG, layer_of(params, layer), x,
                              jnp.ones((128,), bool))
    with jax.default_matmul_precision("highest"):
        want = ref.retention(SPEC, x, weights.layer(layer))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_forward_equals_the_reference(params, weights):
    p = prompt(1, 128)
    got = brumby.forward(CFG, params, jnp.asarray(p))
    want = ref.logits_at(HP, weights, p, list(range(128)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_assumed_scale_inside_the_power(params):
    """((q . k) / sqrt(hd))^2: the program's q carries hd^-1/2, so its
    squared score is the scaled one and not (q . k)^2 / sqrt(hd)."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 64), jnp.float32)
    p = layer_of(params, 0)
    q, k, _, _, _ = brumby.ret_inputs(CFG, p, x, jnp.arange(4))
    unscaled, _, _, _, _ = brumby.ret_inputs(
        dataclasses.replace(CFG, head_dim=16), {**p}, x, jnp.arange(4))
    s = jnp.einsum("tkgd,tkd->tkg", q, k)
    raw = jnp.einsum("tkgd,tkd->tkg", unscaled * 4.0, k)    # hd^1/2 = 4
    np.testing.assert_allclose(s * s, (raw / 4.0) ** 2, rtol=1e-5)


def test_assumed_eps_on_the_normaliser():
    """eps is added to sum_j a_tj: with every weight zero (k = 0) the output
    is 0 / eps = 0, not 0 / 0."""
    q, k, v, gamma = rows(7, 6)
    o = pr.retention_quadratic(q, jnp.zeros_like(k), v, gamma, EPS)
    assert bool(jnp.all(o == 0.0))
    a = jnp.einsum("tkgd,tkd->tkg", q[:1], k[:1]) ** 2
    one = pr.retention_quadratic(q[:1], k[:1], v[:1], gamma[:1], EPS)
    np.testing.assert_allclose(
        one, (a / (a + EPS))[..., None] * v[:1, :, None], rtol=1e-5)


def test_assumed_qk_norm_and_rotary_kept(params):
    """q and k are RMS-normed per head with a learned weight, then rotated
    by position over the whole head: the norm's weight scales them, and the
    score of a pair depends on the distance alone."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 64), jnp.float32)
    x = jnp.tile(x, (6, 1))
    p = layer_of(params, 0)
    q, k, _, _, _ = brumby.ret_inputs(CFG, p, x, jnp.arange(6))
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(k * k, -1)), 1.0, rtol=1e-3)
    s = jnp.einsum("tkgd,jkd->tjkg", q, k)
    np.testing.assert_allclose(s[3, 1], s[5, 3], rtol=1e-4, atol=1e-6)
    assert float(jnp.abs(s[3, 1] - s[3, 2]).max()) > 1e-4
    doubled = {**p, "k_norm": 2.0 * p["k_norm"]}
    _, k2, _, _, _ = brumby.ret_inputs(CFG, doubled, x, jnp.arange(6))
    np.testing.assert_allclose(k2, 2.0 * k, rtol=1e-5)


def test_assumed_gate_bias_zero_is_the_bias_free_gate_and_seeded_heads_remember(
        params):
    """gamma = log sigmoid(w_g . u + b_g). With b_g = 0 it is the bias-free
    gate, which on seeded zero-mean weights forgets in under two positions;
    the seeded b_g ~ U(2, 10) spreads the heads' memories."""
    x = rms = jax.random.normal(jax.random.PRNGKey(4), (256, 64), jnp.float32)
    x = rms / jnp.sqrt(jnp.mean(rms * rms, -1, keepdims=True))
    p = layer_of(params, 0)
    free = brumby.ret_inputs(CFG, {**p, "bg": jnp.zeros_like(p["bg"])}, x,
                             jnp.arange(256))[3]
    np.testing.assert_allclose(free, jax.nn.log_sigmoid(x @ p["wg"]),
                               rtol=1e-5, atol=1e-6)
    assert float(jnp.mean(free)) < -0.6             # e^-0.7 a step
    _, _, _, seeded, gate = brumby.ret_inputs(CFG, p, x, jnp.arange(256))
    assert bool(jnp.all(seeded <= 0.0))
    np.testing.assert_allclose(gate, jnp.exp(seeded), rtol=1e-6)
    assert float(jnp.mean(seeded)) > -0.2
    bg = np.asarray(params["layers"]["bg"])
    assert bg.min() >= 2.0 and bg.max() <= 10.0


# --- the engine -----------------------------------------------------------------


def test_nothing_lies_under_the_block_table_and_slots_bound_admission(params):
    """No cache array has a block axis: the pool's size changes no device
    byte, and with more names than any request needs the slots alone bound
    what is in flight."""
    steps = step_set(CFG)
    assert steps is _brumby_steps
    assert steps.SLOT_STATE == "state" and steps.SNAPSHOT_STATE == "snap_state"
    assert steps.SNAPSHOT_POLICY is SnapshotsAtMatch
    small, large = (steps.alloc_cache(CFG, dataclasses.replace(
        ECFG, num_kv_blocks=n)) for n in (16, 4096))
    assert [a.shape for a in small] == [a.shape for a in large]
    assert small[0].shape == (2, 3 + 1, 2, pr.phi_width(16), 16)
    assert small[2].shape == (4 + 1, 2, 2, pr.phi_width(16), 16)
    engine = PagedEngine(CFG, params, ECFG)

    async def go():
        return await asyncio.gather(*[
            engine.check_routing(prompt(60 + i, 40), 6) for i in range(5)])

    outs = asyncio.run(go())
    assert all(len(o["token_ids"]) == 6 for o in outs)
    s = engine.stats()
    assert s["kv_positions_live"] == 0 and s["rows_decoded"] == 5 * 5
    assert s["state_bytes"] == sum(a.nbytes for a in small[:2])
    assert s["snapshot_bytes"] == sum(a.nbytes for a in small[2:])
    assert s["decode_attention"] == pr.XLA


def test_make_kv_inject_refuses_with_its_reason(engine):
    with pytest.raises(ValueError, match="without keys and values"):
        _brumby_steps.make_kv_inject(CFG, ECFG)

    async def go():
        blocks = (np.zeros((2, 1, 16, 2, 16), np.float32),) * 2
        return [t async for t in engine.generate_stream(
            prompt(1, 10), max_tokens=2,
            prefilled=(*blocks, np.zeros((512,), np.float32)))]

    with pytest.raises(ValueError, match="retention state"):
        asyncio.run(go())


def test_a_prompt_in_chunks_equals_the_whole_prompt_equals_the_reference(
        engine, params, weights):
    """200 tokens = chunks of 64, 64, 64 and 8 (in a 32-row step) through the
    decode step, the state handed from chunk to chunk and to the decode
    rows; the logits are the whole-prompt quadratic form's and the
    reference's."""
    p = prompt(11, 200)
    out, = serve(engine, [p], mechanisms=True)
    assert engine.stats()["prefill_chunks"] == 4
    g = gaps(weights, p, out)
    assert g["argmax_equal"] == 8 and max(g["gaps"]) < 2e-4
    # prompts run as chunks: no whole-prompt program is built, checked or
    # lowered, and the refusal names the family's check
    assert engine._prefill is None
    for refused in (engine.check_prefill, lambda p: engine.step_hlo([len(p)])):
        with pytest.raises(ValueError, match="no whole-prompt.*check_routing"):
            refused(p)
    whole = brumby.forward(CFG, params, jnp.asarray(p))[199]
    assert int(jnp.argmax(whole)) == out["token_ids"][0]
    # the slot's state is the direct sum over the program's own k, v, gamma
    rows_ = ref.recurrence_inputs(out["chunks"], out)
    got = ref.mechanism_readings(rows_, unpack(out["state"]), weights.gates(),
                                 low_rows=7)
    assert got["state_rows"] == 207
    assert got["state_error"] < 1e-5 < 1e-3 < got["state_error_bf16"]
    assert got["gamma_error"] < 1e-5 < 1e-3 < got["gamma_error_bf16"]


def test_a_request_resumed_from_a_snapshot_another_request_left_equals_a_run_from_position_0(
        engine, weights):
    """The policy: the first prompt behind a header leaves nothing; the
    second finds the header's blocks with no snapshot near their end, runs
    them again, ends a chunk on the match's end and leaves the one snapshot
    there; the third resumes from it, and its answer is a cold run's."""
    header = prompt(21, 150)
    a, b, c = (header + prompt(22 + i, 21 + 6 * i) for i in range(3))
    first, second = serve(engine, [a, b])
    s = engine.stats()
    assert (first["resume_from"], second["resume_from"]) == (0, 0)
    assert s["snapshots_taken"] == 1 and s["snapshots_restored"] == 0
    assert s["snapshot_rerun_tokens"] == 144          # nine blocks of 16
    # b's chunks: 64, 64, then 16 up to the match's end, then the rest
    warm, = serve(engine, [c], mechanisms=True)
    s = engine.stats()
    assert warm["resume_from"] == 144 and s["snapshots_restored"] == 1
    assert s["snapshots_shared"] == 1 and s["snapshot_rerun_tokens"] == 144
    assert s["snapshots_taken"] == 1                  # c leaves none of its own
    cold, = serve(engine, [c], cold=True, mechanisms=True)
    assert cold["resume_from"] == 0
    # (float32 here: the chunks are cut elsewhere and the tokens still agree)
    assert warm["token_ids"] == cold["token_ids"]
    g = gaps(weights, c, warm)
    assert g["argmax_equal"] == 8 and max(g["gaps"]) < 2e-4
    # the state: the header's chunks (the cold run's rows), the snapshot, the
    # restore, the item's chunk and seven decode steps
    head = ref.recurrence_inputs(cold["chunks"])
    tail = ref.recurrence_inputs(warm["chunks"], warm)
    rows_ = {k: np.concatenate([head[k][:144], tail[k]]) for k in tail}
    got = ref.mechanism_readings(rows_, unpack(warm["state"]),
                                 weights.gates(), low_rows=tail["k"].shape[0])
    assert got["state_rows"] == len(c) + 7
    assert got["state_error"] < 1e-5 < 1e-3 < got["state_error_bf16"]
    assert engine._probe_slot is None


def test_assumed_float32_state_a_bf16_state_fails(engine, weights, monkeypatch):
    """A computation one precision lower fails the tolerances: the reference
    with float8 activations lies outside the logit limit, and a program
    whose state is rounded to bf16 after every step outside the state's."""
    p = prompt(31, 120)
    out, = serve(engine, [p])
    ok = gaps(weights, p, out)
    low = gaps(weights, p, out, activations=jnp.float8_e4m3fn)
    step = 2.0 ** -8 * max(1.0, ok["max_abs_logit"])
    # (two layers of 64 channels: the chip's readings at the published widths
    # are in PERF.md section 6)
    assert max(ok["gaps"]) / step < 0.1 < 1.0 < max(low["gaps"]) / max(
        step, 2.0 ** -8 * low["max_abs_logit"])
    real = pr.retention_step_xla

    def rounded(*a):
        o, S, Z = real(*a)
        return (o, S.astype(jnp.bfloat16).astype(jnp.float32),
                Z.astype(jnp.bfloat16).astype(jnp.float32))

    monkeypatch.setattr(pr, "retention_step_xla", rounded)
    broken = PagedEngine(CFG, engine.params, ECFG)
    out, = serve(broken, [p], max_tokens=40, mechanisms=True)
    rows_ = ref.recurrence_inputs(out["chunks"], out)
    got = ref.mechanism_readings(rows_, unpack(out["state"]), weights.gates(),
                                 low_rows=0)
    assert got["state_error"] > 1e-3


def test_a_dropped_hand_over_shows_in_the_logits(engine, weights, monkeypatch):
    """Planted: the chunks start from zeros, not from what the chunk before
    left. The reference from position 0 does not agree."""
    real = pr.retention_chunked
    monkeypatch.setattr(
        pr, "retention_chunked", lambda q, k, v, g, S, Z, eps: real(
            q, k, v, g, jnp.zeros_like(S), jnp.zeros_like(Z), eps))
    broken = PagedEngine(CFG, engine.params, ECFG)
    p = prompt(41, 200)
    out, = serve(broken, [p])
    assert max(gaps(weights, p, out)["gaps"]) > 1e-2


def test_three_headers_in_turn_each_keep_one_snapshot_of_four(engine, weights):
    """4 entries, 3 headers, four prompts behind each in turn: a header keeps
    exactly one snapshot, at its blocks' end, no prompt leaves one along its
    own length, and nothing is displaced."""
    cache = engine._prefix_cache
    for h in range(3):
        header = prompt(70 + h, 130 + 16 * h)
        outs = serve(engine, [header + prompt(80 + 10 * h + i, 20 + i)
                              for i in range(4)], max_tokens=4)
        assert [o["resume_from"] for o in outs] == [0, 0] + [
            (len(header) // 16) * 16] * 2
        s = engine.stats()
        assert s["snapshots_taken"] == h + 1 == s["prefix_cache"]["snapshots"]
        assert s["snapshots_evicted"] == 0
        assert s["snapshots_restored"] == s["snapshots_shared"] == 2 * (h + 1)
    assert len(cache._snap_key) == 3 and len(cache._free_snaps) == 1


def test_prompts_behind_one_still_in_chunks_wait_and_nothing_runs_a_third_time(
        engine):
    """Three prompts behind one header arrive together: the second waits
    until the first has run what they share (admitted at once it would match
    the part registered so far, run the rest itself and leave a snapshot half
    way), then runs the header again and leaves the snapshot; the third waits
    for that and resumes from it."""
    header = prompt(61, 150)
    a, b, c = (header + prompt(62 + i, 25 + i) for i in range(3))

    async def go():
        return await asyncio.gather(*[engine.check_routing(p, 6)
                                      for p in (a, b, c)])

    outs = asyncio.run(go())
    assert [o["resume_from"] for o in outs] == [0, 0, 144]
    s = engine.stats()
    assert s["snapshots_taken"] == 1 and s["snapshots_shared"] == 1
    assert s["prefill_chunk_tokens"] == len(a) + len(b) + len(c) - 144
    alone = serve(PagedEngine(CFG, engine.params, ECFG), [a, b, c],
                  max_tokens=6)
    assert [o["token_ids"] for o in outs] == [o["token_ids"] for o in alone]


def test_an_evicted_snapshot_shortens_the_match_and_the_answer_stays_right(
        engine, weights):
    header = prompt(91, 150)
    a, b, c = (header + prompt(92 + i, 25) for i in range(3))
    serve(engine, [a, b], max_tokens=4)
    cache = engine._prefix_cache
    assert engine.stats()["snapshots_taken"] == 1
    # the pool gives the header's snapshot up (as a displacement would)
    key = cache._snap_key[next(iter(cache._snap_key))]
    cache._drop(key)
    out, = serve(engine, [c])
    s = engine.stats()
    # the blocks still match; with no snapshot the prompt runs from 0 again
    # and leaves the snapshot once more
    assert out["resume_from"] == 0 and s["snapshots_restored"] == 0
    assert s["snapshots_taken"] == 2 and s["prefix_cache"]["block_hits"] == 18
    g = gaps(weights, c, out)
    assert g["argmax_equal"] == 8 and max(g["gaps"]) < 2e-4
    # evicted with its blocks: it never outlives them
    freed = cache.evict(10 ** 6)
    assert freed and not cache._snap_key
    assert s["snapshots_evicted"] + 1 == engine.stats()["snapshots_evicted"]


def test_requests_in_one_batch_do_not_touch_each_other(engine):
    a, b = prompt(51, 200), prompt(52, 90)
    alone = serve(PagedEngine(CFG, engine.params, ECFG), [a])[0]["token_ids"]

    async def go():
        return await asyncio.gather(engine.check_routing(a, 8),
                                    engine.check_routing(b, 8))

    together, _ = asyncio.run(go())
    assert together["token_ids"] == alone


def test_llm_config_resolves_the_family():
    cfg, params = LLMConfig(model="brumby:tiny").build_model()
    assert isinstance(cfg, brumby.BrumbyConfig)
    assert params["layers"]["wqkv"].shape == (2, 64, (4 + 2 * 2) * 16)
    big = brumby.BrumbyConfig.brumby_14b()
    assert (big.dim, big.n_layers, big.n_heads, big.n_kv_heads, big.head_dim,
            big.ffn_dim, big.vocab_size) == (5120, 40, 40, 8, 128, 17408,
                                             151936)
    assert big.state_width == 8704

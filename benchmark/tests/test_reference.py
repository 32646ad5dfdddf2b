"""The plain reference agrees with models.llama.forward in float32 to
rounding; prefill-then-decode through PagedEngine passes the teacher-forced
logit check; a deliberately wrong position or mask fails it."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference
from benchmark.runners import _inside, serve_dp

HP = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
      "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 512,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


@pytest.fixture(scope="module")
def model():
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig.tiny(dtype=jnp.float32, param_dtype=jnp.float32)
    assert (cfg.dim, cfg.ffn_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers,
            cfg.vocab_size) == (128, 256, 4, 2, 2, 512)
    params = init_params(cfg, jax.random.key(0))
    return cfg, params, _inside.ProgramWeights(params)


def test_reference_matches_llama_forward_in_float32(model):
    from ray_tpu.models.llama import forward

    cfg, params, weights = model
    tokens = np.asarray(jax.random.randint(jax.random.key(1), (70,), 0, 512))
    want = np.asarray(forward(cfg, params, tokens[None]))[0]
    got = reference.logits_at(HP, weights, tokens, range(len(tokens)))
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    # padding on the right changes no real position under a causal mask
    padded = reference.logits_at(HP, weights, reference.pad_to_multiple(tokens, 64),
                                 range(len(tokens)))
    assert np.abs(padded - got).max() <= 1e-5


def test_reference_loss_and_grad_norm_match_autodiff(model):
    from ray_tpu.models.llama import loss_fn

    cfg, params, weights = model
    tokens = np.asarray(jax.random.randint(jax.random.key(2), (48,), 0, 512))
    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(cfg, p, jnp.asarray(tokens)[None]))(params)
    norm = float(jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads))))
    got = reference.loss_and_grad_norm(HP, weights, tokens)
    assert got["loss"] == pytest.approx(float(loss), rel=1e-5)
    assert got["grad_norm"] == pytest.approx(norm, rel=1e-4)


def generate(cfg, params, prompt, n):
    from ray_tpu.llm._engine import EngineConfig, PagedEngine

    engine = PagedEngine(cfg, params, EngineConfig(
        max_num_seqs=2, kv_block_size=16, num_kv_blocks=16, max_model_len=128))

    async def go():
        # twice: the second answer comes through suffix prefill over the
        # first one's cached blocks
        out = []
        for _ in range(2):
            out.append([t async for t in engine.generate_stream(
                prompt, max_tokens=n, temperature=0.0)])
        assert engine.stats()["prefix_cache"]["block_hits"] > 0
        return out

    return asyncio.run(go())


def verdict(weights, prompt, answer):
    gaps = reference.teacher_forced_gaps(HP, weights, prompt, answer, 64)
    return serve_dp.judge_check([gaps], serve_dp.CHECK_TOLERANCE_BF16_STEPS)


def test_paged_engine_passes_the_teacher_forced_check(model):
    cfg, params, weights = model
    prompt = [256] + list(np.asarray(
        jax.random.randint(jax.random.key(3), (40,), 0, 256)))
    for answer in generate(cfg, params, prompt, 12):
        assert len(answer) == 12
        v = verdict(weights, prompt, answer)
        assert v["ok"], v
        assert v["worst_gap"] < 1e-3


def test_a_wrong_decode_position_fails_the_check(model, monkeypatch):
    from ray_tpu.llm import _engine

    cfg, params, weights = model
    real = _engine.rope_tables

    def shifted(c, positions):
        # decode steps (one position a slot) see every position 7 too late
        if positions.ndim == 2 and positions.shape[1] == 1:
            positions = positions + 7
        return real(c, positions)

    monkeypatch.setattr(_engine, "rope_tables", shifted)
    prompt = [256] + list(np.asarray(
        jax.random.randint(jax.random.key(3), (40,), 0, 256)))
    answer = generate(cfg, params, prompt, 12)[0]
    v = verdict(weights, prompt, answer)
    assert not v["ok"], v


def test_a_wrong_mask_fails_the_check(model, monkeypatch):
    """A system whose attention sees only the last 4 keys: the reference with
    its mask narrowed generates greedily, and the true reference refuses the
    result."""
    cfg, params, weights = model
    prompt = [256] + list(np.asarray(
        jax.random.randint(jax.random.key(4), (40,), 0, 256)))

    def windowed(q, k, v):
        T, H, hd = q.shape
        k, v = (jnp.repeat(x, H // k.shape[1], axis=1) for x in (k, v))
        s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        i = jnp.arange(T)
        keep = (i[None, :] <= i[:, None]) & (i[None, :] > i[:, None] - 4)
        s = jnp.where(keep[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    monkeypatch.setattr(reference, "causal_attention", windowed)
    reference._jitted_layer.cache_clear()
    seq = list(prompt)
    for _ in range(12):
        seq.append(int(reference.logits_at(HP, weights, seq, [len(seq) - 1])[0].argmax()))
    monkeypatch.undo()
    reference._jitted_layer.cache_clear()
    v = verdict(weights, prompt, seq[len(prompt):])
    assert not v["ok"], v

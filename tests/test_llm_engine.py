"""Continuous batching + paged KV engine (reference: vllm_engine.py:283):
concurrent streaming completions with mid-decode admission, block reuse,
parity with the dense decoder, and prompts admitted as chunks that ride in
the decode steps."""

import ast
import asyncio
import dataclasses
import functools
import importlib
import inspect
import re
import textwrap
import types
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu.llm import (
    EOS, MODEL_FAMILIES, LLMConfig, LLMEngine, _engine, step_set,
)
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.models.llama import LlamaConfig, init_params

CFG = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32)


def test_paged_matches_dense_decode():
    from ray_tpu.llm._generate import generate

    params = init_params(CFG, jax.random.PRNGKey(0))
    prompts = [[1, 5, 9], [3, 3, 3, 7, 2], [42]]
    dense = generate(CFG, params, prompts, max_new_tokens=8, temperature=0.0)
    eng = PagedEngine(CFG, params, EngineConfig(
        max_num_seqs=3, kv_block_size=4, num_kv_blocks=32, max_model_len=64))

    async def run_one(p):
        return [t async for t in eng.generate_stream(
            p, max_tokens=8, temperature=0.0)]

    async def main():
        return await asyncio.gather(*[run_one(p) for p in prompts])

    paged = asyncio.run(main())
    assert paged == dense
    # every block returned to the pool
    assert eng.stats()["free_blocks"] == 32


@pytest.mark.parametrize("requests,steps,live", [
    # a request of P prompt tokens and N answer tokens takes N steps: one
    # that carries its prompt as a chunk (every slot inactive: it reads no
    # cached position) and hands out the first token, then N - 1 decode
    # steps, of which step j reads the P + j cached positions and the
    # current token's
    ([(3, 5)], 5, 4 + 5 + 6 + 7),
    ([(3, 5), (5, 3)], 8, (4 + 5 + 6 + 7) + (6 + 7)),
], ids=["one_request", "two_in_turn"])
def test_stats_count_live_and_dense_attention_positions(requests, steps, live):
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = PagedEngine(CFG, params, EngineConfig(
        max_num_seqs=2, kv_block_size=4, num_kv_blocks=32, max_model_len=64))

    async def main():
        for plen, n in requests:          # one after the other: exact counts
            out = [t async for t in eng.generate_stream(
                list(range(1, plen + 1)), max_tokens=n, temperature=0.0)]
            assert len(out) == n

    asyncio.run(main())
    stats = eng.stats()
    assert stats["decode_attention"] == "xla"      # the CPU: no kernel
    assert "decode_attention_note" not in stats
    assert stats["steps"] == steps
    assert stats["attn_positions_live"] == live
    # what scoring max_model_len positions of every slot reads
    assert stats["attn_positions_dense"] == steps * 2 * 64


# ---------------------------------------------------------------------------
# prompts as chunks of the decode steps
# ---------------------------------------------------------------------------

CHUNK_ECFG = EngineConfig(max_num_seqs=3, kv_block_size=4, num_kv_blocks=64,
                          max_model_len=64, prefix_cache=False)
LADDER = (8, 16)
# on and around every chunk width and block edge, several chunks of the
# widest, and the longest prompt the engine takes
LENGTHS = (1, 3, 4, 5, 7, 8, 9, 15, 16, 17, 2 * 16 + 3, 64 - 1)
ANSWER = 6
TEMPERATURE, SEED = 0.8, 11


def a_prompt(n, salt=0):
    return [int(t) for t in
            np.random.default_rng(1000 * salt + n).integers(1, 500, n)]


def test_the_ladder_of_chunk_widths():
    from ray_tpu.llm._engine import chunk_ladder

    assert chunk_ladder(CHUNK_ECFG) == LADDER
    # the serve cells' engine: two programs beside the plain decode step
    assert chunk_ladder(EngineConfig(max_model_len=2048)) == (128, 256)


@pytest.mark.parametrize("seed,rid", [(0, 1), (7, 3), (2 ** 31 - 1, 9),
                                      (2903000608, 41), (2 ** 40 + 5, 1000)])
def test_request_key_is_prngkey_of_the_seed_formula(seed, rid):
    """The step draws a request's first token with the key `_sample_first`
    made by `jax.random.PRNGKey`; the loop writes that key's data itself."""
    from ray_tpu.llm._engine import _Request, _request_key

    got = _request_key(_Request(rid, [1], 1, 0.0, seed))
    want = jax.random.key_data(jax.random.PRNGKey(seed * 1000003 + rid))
    assert list(np.asarray(want)) == list(got)


@pytest.fixture(scope="module")
def chunk_params():
    return init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def served(chunk_params):
    """Every prompt length through one engine, greedy and seeded, admitted
    at an idle engine and while another slot decodes: {(mode, plen,
    temperature): (rid, tokens)}, the background requests' tokens, and the
    engine's `stats()` and count of compiled step programs at the end."""
    eng = PagedEngine(CFG, chunk_params, CHUNK_ECFG)
    out, background = {}, []

    async def one(prompt, temperature, n=ANSWER):
        rid = eng._rid + 1
        toks = [t async for t in eng.generate_stream(
            prompt, max_tokens=n, temperature=temperature, seed=SEED)]
        return rid, toks

    async def main():
        for plen in LENGTHS:
            for temperature in (0.0, TEMPERATURE):
                out["idle", plen, temperature] = await one(
                    a_prompt(plen), temperature)
        for plen in LENGTHS:
            # a slot that decodes all through the admissions of this length
            long = a_prompt(6, salt=plen)
            gen = eng.generate_stream(long, max_tokens=40)
            head = [await gen.__anext__() for _ in range(2)]
            for temperature in (0.0, TEMPERATURE):
                out["busy", plen, temperature] = await one(
                    a_prompt(plen), temperature)
            background.append((long, head + [t async for t in gen]))

    asyncio.run(main())
    return out, background, eng.stats(), eng._decode._cache_size()


def greedy_reference(params, prompts, n):
    from ray_tpu.llm._generate import generate

    return generate(CFG, params, prompts, max_new_tokens=n, temperature=0.0)


@pytest.mark.parametrize("mode", ["idle", "busy"])
@pytest.mark.parametrize("plen", LENGTHS)
def test_chunked_admission_equals_generate_greedy(served, chunk_params, mode,
                                                  plen):
    _, toks = served[0][mode, plen, 0.0]
    # the longest prompt leaves room for one token under max_model_len
    want = greedy_reference(chunk_params, [a_prompt(plen)], ANSWER)[0]
    assert toks == want[:min(ANSWER, 64 - plen)]


@pytest.mark.parametrize("mode", ["idle", "busy"])
@pytest.mark.parametrize("plen", LENGTHS)
def test_chunked_admission_draws_the_seeded_tokens(served, chunk_params, mode,
                                                   plen):
    """Temperature > 0: token j is the draw from the dense forward pass's
    logits with the request's own keys: `PRNGKey(seed * 1000003 + rid)` for
    the first, then that key folded with 7 and counted up a step."""
    from ray_tpu.models.llama import forward

    rid, toks = served[0][mode, plen, TEMPERATURE]
    assert len(toks) == min(ANSWER, 64 - plen)
    seq = np.zeros((1, 64 + ANSWER), np.int32)       # one compiled shape
    seq[0, :plen + len(toks)] = a_prompt(plen) + toks
    logits = jax.jit(lambda p, x: forward(CFG, p, x))(chunk_params, seq)[0]
    key = jax.random.PRNGKey(SEED * 1000003 + rid)
    stream = np.asarray(jax.random.key_data(jax.random.fold_in(key, 7)))
    for j, tok in enumerate(toks):
        if j:
            key = jax.random.wrap_key_data(
                stream + np.asarray([0, j - 1], np.uint32))
        want = jax.random.categorical(
            key, logits[plen - 1 + j].astype(jnp.float32) / TEMPERATURE)
        assert int(want) == tok, (j, toks)


def test_slots_decoding_beside_the_admissions_are_undisturbed(served,
                                                              chunk_params):
    _, background, _, _ = served
    prompts = [p for p, _ in background]
    assert [t for _, t in background] == greedy_reference(
        chunk_params, prompts, 40)


def test_mixed_traffic_compiles_the_ladder_and_no_more(served):
    """Whatever the prompts' lengths and the batch's state, the loop has
    dispatched the decode step at the ladder's widths and without a chunk:
    three programs."""
    _, _, stats, programs = served
    assert programs == len(LADDER) + 1
    sent = 2 * 2 * sum(LENGTHS) + 6 * len(LENGTHS)
    assert stats["prefill_chunk_tokens"] == sent
    assert stats["prefill_chunks"] == stats["steps_with_chunk"] < stats["steps"]
    assert stats["free_blocks"] == 64


def test_warm_up_compiles_every_program_before_the_first_request(
        chunk_params):
    eng = PagedEngine(CFG, chunk_params, CHUNK_ECFG)
    eng.warm_up()
    assert eng._decode._cache_size() == len(LADDER) + 1
    assert eng.stats()["steps"] == 0

    async def main():
        return [t async for t in eng.generate_stream(
            a_prompt(2 * 16 + 3), max_tokens=ANSWER)]

    assert [asyncio.run(main())] == greedy_reference(
        chunk_params, [a_prompt(2 * 16 + 3)], ANSWER)
    assert eng._decode._cache_size() == len(LADDER) + 1


def test_every_active_slot_gets_a_token_in_each_step_of_a_long_admission(
        chunk_params):
    """A prompt of four chunks admitted beside a decoding slot: the chunks
    ride in that slot's steps. The slot's 20 tokens take 20 steps (its own
    prompt's chunk, then 19 decode steps) whether or not the long prompt is
    admitted meanwhile, so it got a token in each of the long prompt's four
    steps."""
    eng = PagedEngine(CFG, chunk_params, CHUNK_ECFG)
    long = a_prompt(3 * 16 + 5)

    async def main():
        gen = eng.generate_stream(a_prompt(5), max_tokens=20)
        head = [await gen.__anext__() for _ in range(3)]
        first = [t async for t in eng.generate_stream(long, max_tokens=1)]
        at_first = eng.stats()
        return head + [t async for t in gen], first, at_first

    toks, first, at_first = asyncio.run(main())
    assert len(toks) == 20
    assert [first] == greedy_reference(chunk_params, [long], 1)
    # the long prompt's first token came in its fourth step, by when the
    # decoding slot had its own chunk's token and one from every step since
    assert at_first["steps_with_chunk"] == 1 + 4
    assert at_first["tokens_out"] == at_first["steps"] + 1
    stats = eng.stats()
    assert stats["steps"] == 20 and stats["tokens_out"] == 21
    assert stats["prefill_chunk_tokens"] == 5 + len(long)
    assert stats["prefill_chunk_pad_tokens"] == (8 - 5) + (8 - 5)


def test_block_reuse_across_waves():
    """More sequences over time than the pool could ever hold at once."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    eng = PagedEngine(CFG, params, EngineConfig(
        max_num_seqs=2, kv_block_size=4, num_kv_blocks=8, max_model_len=24))

    async def run_one(i):
        return [t async for t in eng.generate_stream(
            [i % 100 + 1, i % 50], max_tokens=6, temperature=0.0)]

    async def main():
        return await asyncio.gather(*[run_one(i) for i in range(10)])

    outs = asyncio.run(main())
    assert len(outs) == 10 and all(len(o) == 6 for o in outs)
    assert eng.stats()["free_blocks"] == 8


@pytest.fixture(scope="module")
def ray_init():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


def test_concurrent_streaming_mid_decode_admission(ray_init):
    """The VERDICT done-criterion: N concurrent streaming completions with
    at least one admitted mid-decode, tokens/s reported."""
    config = LLMConfig(model="tiny", model_overrides=dict(
        dtype=jnp.float32, param_dtype=jnp.float32))
    eng = LLMEngine.remote(config, EngineConfig(
        max_num_seqs=4, kv_block_size=8, num_kv_blocks=64, max_model_len=96))

    # first request starts decoding alone...
    g1 = eng.completions_stream.remote("hello world", max_tokens=40)
    first_tokens = [ray_tpu.get(next(g1), timeout=120) for _ in range(3)]
    assert len(first_tokens) == 3
    # ...then three more arrive MID-decode and join the running batch
    gens = [
        eng.completions_stream.remote(f"prompt {i}", max_tokens=10)
        for i in range(3)
    ]
    outs = []
    for g in gens:
        outs.append([ray_tpu.get(r, timeout=120) for r in g])
    rest1 = [ray_tpu.get(r, timeout=120) for r in g1]
    assert all(len(o) > 0 for o in outs)
    assert len(first_tokens) + len(rest1) <= 40
    stats = ray_tpu.get(eng.stats.remote(), timeout=60)
    assert stats["mid_decode_admissions"] >= 1, stats
    assert stats["tokens_per_s"] > 0, stats
    print("engine stats:", stats)
    ray_tpu.kill(eng)


def test_disaggregated_prefill_matches_local():
    """P/D disaggregation: prefill computed in a DIFFERENT pool and
    injected into the decode engine must produce the SAME greedy tokens as
    a locally-prefilled request (the KV-transfer correctness bar)."""
    import numpy as np

    from ray_tpu.llm._engine import _make_prefill

    params = init_params(CFG, jax.random.PRNGKey(0))
    ecfg = EngineConfig(max_num_seqs=2, kv_block_size=4, num_kv_blocks=32,
                        max_model_len=64)
    prompts = [[1, 5, 9, 2, 8], [7, 7, 3]]

    # local baseline
    eng_local = PagedEngine(CFG, params, ecfg)

    async def run_local(p):
        return [t async for t in eng_local.generate_stream(
            p, max_tokens=8, temperature=0.0)]

    local = [asyncio.run(run_local(p)) for p in prompts]

    # remote-style prefill: tiny standalone pool, contents shipped as numpy
    prefill = _make_prefill(CFG, ecfg)
    eng_decode = PagedEngine(CFG, params, ecfg)

    def remote_prefill(p):
        bs = ecfg.kv_block_size
        nb = -(-len(p) // bs)
        S = max(8, 1 << (len(p) - 1).bit_length())
        hd = CFG.head_dim
        kc = jnp.zeros((CFG.n_layers, nb + 1, bs, CFG.n_kv_heads, hd),
                       CFG.dtype)
        vc = jnp.zeros_like(kc)
        table = np.arange(1, nb + 1, dtype=np.int32)
        prompt = np.zeros((S,), np.int32)
        prompt[:len(p)] = p
        logits, kc, vc = prefill(S, params, kc, vc, jnp.asarray(table),
                                 jnp.asarray(prompt), jnp.int32(len(p)))
        return (np.asarray(kc[:, 1:nb + 1]), np.asarray(vc[:, 1:nb + 1]),
                np.asarray(logits))

    async def run_disagg(p):
        kv = remote_prefill(p)
        return [t async for t in eng_decode.generate_stream(
            p, max_tokens=8, temperature=0.0, prefilled=kv)]

    disagg = [asyncio.run(run_disagg(p)) for p in prompts]
    assert disagg == local
    assert eng_decode.stats()["free_blocks"] == 32  # blocks all returned


def test_kv_aware_router_prefix_affinity():
    from ray_tpu.llm.serving_patterns import KvAwareRouter

    r = KvAwareRouter(n=3, block=4)
    a1, _ = r.pick([1, 2, 3, 4, 99])
    a2, _ = r.pick([1, 2, 3, 4, 55, 77])   # same block-aligned prefix
    assert a1 == a2, "shared prefix must route to the same replica"
    r.done(a1)
    b1, _ = r.pick([9, 9, 9, 9])           # new prefix -> least loaded
    assert b1 != a1 or r.load[a1] <= min(r.load)
    # load accounting drains
    r.done(a2)
    r.done(b1)
    assert all(v == 0 for v in r.load)


# ---------------------------------------------------------------------------
# the step set: what a model family gives the engine (llm/_engine.py's
# docstring), held by every registered family and by one defined here
# ---------------------------------------------------------------------------


def _own_public_names(steps):
    """The names a step set defines itself: a module's imports and private
    helpers are not its interface."""
    home = getattr(steps, "__name__", None)
    return {n for n, v in vars(steps).items()
            if not n.startswith("_") and not inspect.ismodule(v)
            and (home is None or getattr(v, "__module__", home) == home)}


def _engine_code():
    """The nodes of `PagedEngine`'s code, its docstrings aside."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(PagedEngine)))
    for node in ast.walk(tree):
        if isinstance(node, (ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            if ast.get_docstring(node) is not None:
                node.body = node.body[1:]
    return list(ast.walk(tree))


@pytest.mark.parametrize("family", sorted(MODEL_FAMILIES))
def test_every_family_keeps_the_step_set_and_the_engine_names_none(family):
    module, config_cls, _, where = MODEL_FAMILIES[family]
    cfg_cls = getattr(importlib.import_module(module), config_cls)
    steps = step_set(cfg_cls.tiny())
    assert _own_public_names(steps) == set(_engine.STEP_SET)
    for name in ("CACHE_NAMES", "COUNTERS", "PROBE"):
        assert all(isinstance(n, str) for n in getattr(steps, name))
        assert isinstance(getattr(steps, name), tuple)
    assert steps.SLOT_STATE is None or steps.SLOT_STATE in steps.CACHE_NAMES
    assert isinstance(steps.NO_PREFIX_CACHE, (str, type(None)))
    ladder = steps.chunk_ladder(EngineConfig())
    assert isinstance(ladder, tuple) and list(ladder) == sorted(set(ladder))
    for name in ("alloc_cache", "make_decode_step", "chunk_ladder",
                 "make_prefill", "check_prefill", "make_kv_inject",
                 "extra_stats"):
        assert callable(getattr(steps, name))
    # the engine's code: no family, no config class, no step set by name, no
    # cache array as an attribute, no test of a config's class
    family_words = {family, config_cls, where.rpartition(".")[2],
                    where.rpartition(":")[2], "_recurrent"}
    nodes = _engine_code()
    names = {n.id for n in nodes if isinstance(n, ast.Name)} | {
        n.attr for n in nodes if isinstance(n, ast.Attribute)} | {
        a.name for n in nodes if isinstance(n, (ast.Import, ast.ImportFrom))
        for a in n.names}
    assert not {n for n in names for w in family_words
                if re.search(rf"(^|_){re.escape(w)}(_|$)", n, re.I)}
    assert not names & set(steps.CACHE_NAMES)
    assert {n.args[1].id for n in nodes if isinstance(n, ast.Call)
            and getattr(n.func, "id", "") == "isinstance"} <= {"Exception"}


@dataclasses.dataclass(frozen=True)
class ToyConfig:
    """A third family, served by the engine as it stands: a bag of tokens.
    A sequence's state is the sum of its tokens' embeddings (a per-slot
    array beside the pool, whose blocks hold nothing); its next token's
    logits are that sum through the head. Integer-valued float32 weights:
    every sum is exact, so the engine's tokens equal the plain reference's
    whatever the order of summation."""

    vocab_size: int = 320
    dim: int = 16
    dtype: Any = jnp.float32

    @classmethod
    def tiny(cls, **overrides):
        return cls(**overrides)


def toy_params(cfg, key):
    def draw(k, shape):
        return jax.random.randint(k, shape, -4, 5).astype(cfg.dtype)

    emb, head = jax.random.split(key)
    return {"emb": draw(emb, (cfg.vocab_size, cfg.dim)),
            "head": draw(head, (cfg.dim, cfg.vocab_size))}


def _toy_decode_step(cfg, ecfg):
    def paged_decode_step(params, bag, tables, lens, active, last_tok, keys,
                          temps):
        add = jnp.where(active[:, None], params["emb"][last_tok], 0)
        bag = bag.at[0].add(add)
        toks = _engine.sample_tokens(keys, bag[0] @ params["head"], temps)
        rows = jnp.sum(active).astype(jnp.int32)[None]
        return jnp.concatenate([toks, rows]), bag

    return jax.jit(paged_decode_step, donate_argnums=(1,)), "none", None


def _toy_prefill(cfg, ecfg):
    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def paged_prefill(S, params, bag, table, prompt, plen, slot):
        real = (jnp.arange(S) < plen)[:, None]
        total = jnp.sum(jnp.where(real, params["emb"][prompt], 0), axis=0)
        bag = bag.at[0, slot].set(total)
        return (total @ params["head"], jnp.zeros((0, S, 1), jnp.int32), bag)

    return paged_prefill


def _toy_alloc(cfg, ecfg):
    return (jnp.zeros((1, ecfg.max_num_seqs, cfg.dim), cfg.dtype),)


def toy_reference(params, prompt, n):
    emb, head = np.asarray(params["emb"]), np.asarray(params["head"])
    bag, out = emb[prompt].sum(0), []
    for _ in range(n):
        out.append(int(np.argmax(bag @ head)))
        bag = bag + emb[out[-1]]
    return out


def _toy_check_prefill(cfg, ecfg, prefill, params, prompt_ids):
    S = max(8, 1 << (len(prompt_ids) - 1).bit_length())
    prompt = np.zeros((S,), np.int32)
    prompt[:len(prompt_ids)] = prompt_ids
    got = prefill(S, params, *_toy_alloc(cfg, dataclasses.replace(
        ecfg, max_num_seqs=1)), jnp.zeros((1,), jnp.int32),
        jnp.asarray(prompt), jnp.int32(len(prompt_ids)), jnp.int32(0))[0]
    return got, np.asarray(params["emb"])[prompt_ids].sum(0) @ np.asarray(
        params["head"])


def _toy_no_inject(cfg, ecfg):
    raise ValueError("a bag of tokens is not in the blocks: none to transfer")


TOY_STEPS = types.SimpleNamespace(
    CACHE_NAMES=("bag",), alloc_cache=_toy_alloc,
    make_decode_step=_toy_decode_step, chunk_ladder=lambda ecfg: (),
    make_prefill=_toy_prefill, check_prefill=_toy_check_prefill,
    COUNTERS=("toy_rows",), PROBE=(), SLOT_STATE="bag",
    NO_PREFIX_CACHE="a bag of tokens is not in the blocks: none to share",
    make_kv_inject=_toy_no_inject,
    extra_stats=lambda cfg, cache, live: {"bag_bytes": cache[0].nbytes})
TOY_ECFG = EngineConfig(max_num_seqs=2, kv_block_size=4, num_kv_blocks=16,
                        max_model_len=32)


@pytest.fixture
def toy(monkeypatch):
    """The toy family in the table, and nowhere else, for one test: its
    config and weights come out of `LLMConfig.build_model` as any family's
    do."""
    monkeypatch.setitem(MODEL_FAMILIES, "toy", (
        __name__, "ToyConfig", "toy_params", f"{__name__}:TOY_STEPS"))
    return LLMConfig(model="toy:tiny").build_model()


def _toy_serve(eng, prompts, n, **kw):
    async def one(p):
        return [t async for t in eng.generate_stream(p, max_tokens=n, **kw)]

    async def main():
        return await asyncio.gather(*[one(p) for p in prompts],
                                    return_exceptions=True)

    return asyncio.run(main())


def test_a_third_family_is_served_by_the_engine_as_it_stands(toy):
    cfg, params = toy
    assert type(cfg) is ToyConfig and step_set(cfg) is TOY_STEPS
    eng = PagedEngine(cfg, params, TOY_ECFG)
    # five callers on two slots: every slot is handed on, and a new
    # request's prefill overwrites what the last one left in it
    prompts = [a_prompt(n, salt=7)[:n] for n in (3, 9, 1, 17, 6)]
    prompts = [[t % cfg.vocab_size for t in p] for p in prompts]
    assert _toy_serve(eng, prompts, 7) == [
        toy_reference(params, p, 7) for p in prompts]
    stats = eng.stats()
    assert stats["free_blocks"] == 16 and stats["prefix_cache"] is None
    assert stats["tokens_out"] == 35 and stats["prefill_chunks"] == 0
    # its counter behind the tokens, its own entry, the whole-prompt loop's
    assert 0 < stats["toy_rows"] <= 2 * stats["steps"]
    assert stats["bag_bytes"] == eng.bag.nbytes == 2 * 16 * 4
    assert stats["decode_attention"] == "none" and "loop_stalls" in stats
    out = eng.check_prefill(prompts[3])
    assert out["argmax_equal"] and out["max_abs_diff"] == 0.0
    assert set(eng.step_hlo([5])) == {"jit_paged_decode_step",
                                      "jit_paged_prefill"}
    # a fault that took the donated cache: the next request starts clean
    eng.bag.delete()
    assert eng._device_state_invalid()
    eng._reset_device_state()
    assert not np.asarray(eng.bag).any()
    assert _toy_serve(eng, prompts[:1], 4) == [
        toy_reference(params, prompts[0], 4)]


def test_a_step_set_refuses_in_its_own_words(toy):
    cfg, params = toy
    with pytest.raises(ValueError, match="none to share"):
        PagedEngine(cfg, params, dataclasses.replace(
            TOY_ECFG, prefix_cache=True))
    eng = PagedEngine(cfg, params, TOY_ECFG)
    kv = (np.zeros((1, 1, 4, cfg.dim), np.float32), np.zeros((320,)))
    refused, = _toy_serve(eng, [[5, 6, 7]], 3, prefilled=kv)
    assert isinstance(refused, ValueError)
    assert "none to transfer" in str(refused)
    # the request failed, not the engine
    assert _toy_serve(eng, [[5, 6, 7]], 3) == [
        toy_reference(params, [5, 6, 7], 3)]
    assert eng.stats()["free_blocks"] == 16

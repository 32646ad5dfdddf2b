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
import time
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
from ray_tpu.llm._prefix_cache import SnapshotPolicy
from ray_tpu.models.llama import LlamaConfig, init_params

CFG = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32)


# every head its own keys and values: the packed leaf is three equal thirds
MHA = dataclasses.replace(CFG, n_kv_heads=CFG.n_heads)


@pytest.mark.parametrize("cfg", [CFG, MHA], ids=["gqa", "mha"])
def test_paged_matches_dense_decode(cfg):
    from ray_tpu.llm._generate import generate

    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[1, 5, 9], [3, 3, 3, 7, 2], [42]]
    dense = generate(cfg, params, prompts, max_new_tokens=8, temperature=0.0)
    eng = PagedEngine(cfg, params, EngineConfig(
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


def seeded_reference(params, prompt, rid, n):
    """The `n` tokens request `rid` draws at TEMPERATURE with SEED, each from
    the dense forward pass's logits over the prompt and the draws before
    it."""
    from ray_tpu.models.llama import forward

    fwd = jax.jit(lambda p, x: forward(CFG, p, x))
    key = jax.random.PRNGKey(SEED * 1000003 + rid)
    stream = np.asarray(jax.random.key_data(jax.random.fold_in(key, 7)))
    toks = []
    for j in range(n):
        if j:
            key = jax.random.wrap_key_data(
                stream + np.asarray([0, j - 1], np.uint32))
        seq = np.zeros((1, 64 + ANSWER), np.int32)   # one compiled shape
        seq[0, :len(prompt) + j] = prompt + toks
        logits = fwd(params, seq)[0, len(prompt) - 1 + j]
        toks.append(int(jax.random.categorical(
            key, logits.astype(jnp.float32) / TEMPERATURE)))
    return toks


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
    rid, toks = served[0][mode, plen, TEMPERATURE]
    assert len(toks) == min(ANSWER, 64 - plen)
    assert toks == seeded_reference(chunk_params, a_prompt(plen), rid,
                                    len(toks))


@pytest.mark.parametrize("cfg", [CFG, MHA], ids=["gqa", "mha"])
def test_the_step_takes_q_k_and_v_packed_and_the_engine_keeps_the_given_tree(
        cfg):
    """The Llama family's `step_params`: `wqkv` is [wq | wk | wv] column for
    column, every other leaf is the given tree's own buffer, and
    `engine.params` still answers the names `init_params` gives (the
    benchmark's reference check reads them there)."""
    params = init_params(cfg, jax.random.PRNGKey(3))
    eng = PagedEngine(cfg, params, CHUNK_ECFG)
    assert eng.params is params
    given, packed = params["layers"], eng._step_params["layers"]
    assert set(packed) == set(given) - {"wq", "wk", "wv"} | {"wqkv"}
    hd = cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    assert packed["wqkv"].shape == (cfg.n_layers, cfg.dim, nq + 2 * nkv)
    assert packed["wqkv"].dtype == given["wq"].dtype
    for name, lo, hi in (("wq", 0, nq), ("wk", nq, nq + nkv),
                         ("wv", nq + nkv, nq + 2 * nkv)):
        np.testing.assert_array_equal(packed["wqkv"][..., lo:hi], given[name])
    shared = {**{n: (packed[n], given[n]) for n in packed if n != "wqkv"},
              **{n: (eng._step_params[n], params[n])
                 for n in params if n != "layers"}}
    assert set(shared) == {"ln1", "wo", "ln2", "w1", "w3", "w2", "tok_emb",
                           "norm", "lm_head"}
    assert all(a is b for a, b in shared.values())


@pytest.mark.parametrize("plen", [5, 2 * 16 + 3])
def test_an_mha_model_admitted_in_chunks_equals_generate(plen):
    """`n_kv_heads == n_heads` through the chunked step (a chunk beside a
    decoding slot, then decode rows alone)."""
    from ray_tpu.llm._generate import generate

    params = init_params(MHA, jax.random.PRNGKey(1))
    eng = PagedEngine(MHA, params, CHUNK_ECFG)
    prompts = [a_prompt(6, salt=plen), a_prompt(plen)]

    async def main():
        first = eng.generate_stream(prompts[0], max_tokens=12)
        head = [await first.__anext__() for _ in range(2)]
        second = [t async for t in eng.generate_stream(
            prompts[1], max_tokens=ANSWER)]
        return [head + [t async for t in first], second]

    got = asyncio.run(main())
    want = generate(MHA, params, prompts, max_new_tokens=12, temperature=0.0)
    assert got == [want[0], want[1][:ANSWER]]


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
    """The names a step set defines itself, and those of `STEP_SET` it takes
    from elsewhere under the interface's own name (the shared
    `chunk_ladder`): a module's other imports and private helpers are not
    its interface."""
    home = getattr(steps, "__name__", None)
    return {n for n, v in vars(steps).items()
            if not n.startswith("_") and not inspect.ismodule(v)
            and (home is None or n in _engine.STEP_SET
                 or getattr(v, "__module__", home) == home)}


def _engine_code():
    """The nodes of `PagedEngine`'s code, its docstrings aside."""
    tree = ast.parse(textwrap.dedent(inspect.getsource(PagedEngine)))
    for node in ast.walk(tree):
        if isinstance(node, (ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            if ast.get_docstring(node) is not None:
                node.body = node.body[1:]
    return list(ast.walk(tree))


def _holds_the_step_set(steps):
    """Exactly the names of `STEP_SET`, the whole-prompt pair both or
    neither: both wherever the ladder is empty (the loop's `_admit_whole`
    runs the program), and callable."""
    pair = set(_engine.WHOLE_PROMPT)
    assert pair <= set(_engine.STEP_SET)
    own = _own_public_names(steps)
    assert own | pair == set(_engine.STEP_SET)
    assert own & pair in (set(), pair)
    ladder = steps.chunk_ladder(EngineConfig())
    assert isinstance(ladder, tuple) and list(ladder) == sorted(set(ladder))
    assert ladder or pair <= own
    assert all(callable(getattr(steps, name)) for name in own & pair)


@pytest.mark.parametrize("family", sorted(MODEL_FAMILIES))
def test_every_family_keeps_the_step_set_and_the_engine_names_none(family):
    module, config_cls, _, where = MODEL_FAMILIES[family]
    cfg_cls = getattr(importlib.import_module(module), config_cls)
    steps = step_set(cfg_cls.tiny())
    _holds_the_step_set(steps)
    for name in ("CACHE_NAMES", "COUNTERS", "PROBE"):
        assert all(isinstance(n, str) for n in getattr(steps, name))
        assert isinstance(getattr(steps, name), tuple)
    assert steps.SLOT_STATE is None or steps.SLOT_STATE in steps.CACHE_NAMES
    assert isinstance(steps.NO_PREFIX_CACHE, (str, type(None)))
    for name in ("alloc_cache", "step_params", "make_decode_step",
                 "chunk_ladder", "make_kv_inject", "extra_stats"):
        assert callable(getattr(steps, name))
    # the policy is code the engine calls, not a word it compares
    assert (steps.SNAPSHOT_POLICY is None) == (steps.SNAPSHOT_STATE is None)
    assert steps.SNAPSHOT_POLICY is None or issubclass(
        steps.SNAPSHOT_POLICY, SnapshotPolicy)
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


STALL_KEYS = {"loop_stalls", "loop_stall_s", "loop_stall_admit_s",
              "loop_stall_last_at"}
ACCOUNT_KEYS = {"loop_turn_s", "loop_wait_s", "loop_idle_s", "turns_unwaited",
                "turn_unwaited_s"}


@pytest.mark.parametrize("family", sorted(MODEL_FAMILIES))
def test_every_familys_engine_reports_the_loops_account_and_its_stalls(family):
    """`stats()` of any family's engine holds the loop's account of its
    time, a count and the seconds for every chunk width its step takes (0
    alone without a ladder), and the stall counters: all zero before a
    request, and no other key of those shapes."""
    cfg, params = LLMConfig(model=f"{family}:tiny").build_model()
    ecfg = EngineConfig(max_num_seqs=2, kv_block_size=16, num_kv_blocks=16,
                        max_model_len=64, prefix_cache=False)
    stats = PagedEngine(cfg, params, ecfg).stats()
    widths = (0, *step_set(cfg).chunk_ladder(ecfg))
    by_width = {f"{name}{w}" for name in ("steps_w", "turn_s_w")
                for w in widths}
    assert STALL_KEYS | ACCOUNT_KEYS | by_width <= set(stats)
    assert {k for k in stats
            if re.fullmatch(r"(steps|turn_s)_w\d+", k)} == by_width
    assert not any(stats[k] for k in STALL_KEYS | ACCOUNT_KEYS | by_width)


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
                          temps, prev, fed):
        last_tok, keys = _engine.feed_back(prev, fed, last_tok, keys,
                                           chunked=False)
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
    step_params=lambda cfg, params: params,
    make_decode_step=_toy_decode_step, chunk_ladder=lambda ecfg: (),
    make_prefill=_toy_prefill, check_prefill=_toy_check_prefill,
    COUNTERS=("toy_rows",), PROBE=(), SLOT_STATE="bag",
    NO_PREFIX_CACHE="a bag of tokens is not in the blocks: none to share",
    SNAPSHOT_STATE=None, SNAPSHOT_POLICY=None,
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


def serve_all(eng, prompts, n, **kw):
    """Every prompt in flight at once (arrival order = list order), each for
    `n` tokens (one number, or one a prompt): the streams, or the exception
    a stream ended with."""
    async def one(p, n):
        return [t async for t in eng.generate_stream(p, max_tokens=n, **kw)]

    async def main():
        eng._pending = eng._loop_task = None   # a loop task an event loop
        ns = n if isinstance(n, list) else [n] * len(prompts)
        return await asyncio.gather(*map(one, prompts, ns),
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
    assert serve_all(eng, prompts, 7) == [
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
    assert serve_all(eng, prompts[:1], 4) == [
        toy_reference(params, prompts[0], 4)]


def test_the_whole_prompt_pair_is_both_or_neither_and_an_empty_ladder_needs_it(
        toy, monkeypatch):
    cfg, params = toy
    _holds_the_step_set(TOY_STEPS)
    monkeypatch.delattr(TOY_STEPS, "check_prefill")
    with pytest.raises(AssertionError):
        _holds_the_step_set(TOY_STEPS)          # one of the pair alone
    monkeypatch.delattr(TOY_STEPS, "make_prefill")
    with pytest.raises(AssertionError):
        _holds_the_step_set(TOY_STEPS)          # neither, and no ladder
    with pytest.raises(ValueError, match="make_prefill and check_prefill"):
        PagedEngine(cfg, params, TOY_ECFG)


def test_a_step_set_refuses_in_its_own_words(toy):
    cfg, params = toy
    with pytest.raises(ValueError, match="none to share"):
        PagedEngine(cfg, params, dataclasses.replace(
            TOY_ECFG, prefix_cache=True))
    eng = PagedEngine(cfg, params, TOY_ECFG)
    kv = (np.zeros((1, 1, 4, cfg.dim), np.float32), np.zeros((320,)))
    refused, = serve_all(eng, [[5, 6, 7]], 3, prefilled=kv)
    assert isinstance(refused, ValueError)
    assert "none to transfer" in str(refused)
    # the request failed, not the engine
    assert serve_all(eng, [[5, 6, 7]], 3) == [
        toy_reference(params, [5, 6, 7], 3)]
    assert eng.stats()["free_blocks"] == 16


# ---------------------------------------------------------------------------
# the loop runs one step ahead of its results (`PagedEngine._run_loop`): step
# n+1 is dispatched before step n's tokens are fetched and takes them from
# the device; an end token or an abort is seen a step late
# ---------------------------------------------------------------------------

LING_ECFG = EngineConfig(max_num_seqs=1, kv_block_size=16, num_kv_blocks=8,
                         max_model_len=64)


@pytest.fixture(scope="module")
def ling_model():
    from ray_tpu.models import ling

    cfg = ling.LingConfig.tiny()
    return cfg, ling.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(params=["llama", "ling", "toy"])
def one_slot(request, monkeypatch, chunk_params, ling_model):
    """A family's engine with ONE slot, so that the next request takes the
    slot the last one left: `make(eos_id)` builds it, `first` is 1 where the
    request's first token takes a decode step (its prompt's chunk) and 0
    where the awaited prefill hands it out."""
    if request.param == "llama":
        model = (CFG, chunk_params)
        ecfg = dataclasses.replace(CHUNK_ECFG, max_num_seqs=1)
    elif request.param == "ling":
        model, ecfg = ling_model, LING_ECFG
    else:
        monkeypatch.setitem(MODEL_FAMILIES, "toy", (
            __name__, "ToyConfig", "toy_params", f"{__name__}:TOY_STEPS"))
        model = LLMConfig(model="toy:tiny").build_model()
        ecfg = dataclasses.replace(TOY_ECFG, max_num_seqs=1)

    def make(eos_id=None):
        return PagedEngine(*model, ecfg, eos_id=eos_id)

    return types.SimpleNamespace(
        make=make, first=int(bool(step_set(model[0]).chunk_ladder(ecfg))),
        prompts=[[t % 300 for t in a_prompt(n, salt=3)] for n in (5, 9, 3)])


def until(stream, end):
    return stream[:stream.index(end)] if end in stream else stream


def test_a_sampled_end_token_ends_the_stream_and_frees_the_slot_a_step_late(
        one_slot):
    a, b, _ = one_slot.prompts
    plain_a, plain_b = serve_all(one_slot.make(), [a, b], 8)
    # an end token that request `a` first samples as its k-th + 1
    k = next(i for i in range(2, 8) if plain_a[i] not in plain_a[:i])
    end = plain_a[k]
    eng = one_slot.make(eos_id=end)
    got_a, got_b = serve_all(eng, [a, b], 8)
    # exactly the tokens before it; the request behind it, admitted into the
    # slot it left, gets none of its tokens
    assert got_a == plain_a[:k] and got_b == until(plain_b, end)
    stats = eng.stats()
    # the step dispatched before the end token was fetched carried one more
    # row of the ended sequence: dropped, and the slot returned a step late
    ended_b = len(got_b) < len(plain_b)
    assert stats["rows_dropped"] == 1 + ended_b
    first = one_slot.first
    assert stats["steps"] == (first + k) + 1 + (
        first + len(got_b) + ended_b - 1) + ended_b
    assert stats["tokens_out"] == k + 1 + len(got_b) + ended_b
    assert stats["free_blocks"] == eng.ecfg.num_kv_blocks
    assert not eng.active.any() and eng._flight is None


def test_a_checked_request_that_samples_the_end_token_keeps_state_and_steps(
        ling_model):
    """`check_routing(mechanisms=True)` of a sequence that ends by a token:
    the row dispatched before the token was seen moved the slot's state once
    more, so the steps handed out include it and the reference's scan over
    them arrives at the state read."""
    from benchmark.lib import reference_ling as ref
    from benchmark.runners._inside_ling import ProgramWeightsLing

    p = [t % 300 for t in a_prompt(9, salt=3)]
    plain, = serve_all(PagedEngine(*ling_model, LING_ECFG), [p], 12)
    k = next(i for i in range(3, 12) if plain[i] not in plain[:i])
    eng = PagedEngine(*ling_model, LING_ECFG, eos_id=plain[k])
    out = asyncio.run(eng.check_routing(p, 12, mechanisms=True))
    assert out["token_ids"] == plain[:k]
    got = ref.mechanism_readings(
        out, ProgramWeightsLing(ling_model[1]).routers())
    # k decode steps drew tokens 2..k+1 (the prefill drew the first), the
    # last of them the end token, and one more ran before it was seen
    assert got["state_steps"] == k + 1 and eng._probe_slot is None
    assert got["state_error"] < 1e-5 and got["router_f32_steps"] < 32.0


def test_a_stop_the_host_can_count_drops_no_row_and_frees_the_slot_at_once(
        one_slot):
    """`max_tokens` (and `max_model_len`) end a sequence at the dispatch of
    its last step: the request behind it is admitted in the very next turn,
    as when the loop waited for every step's tokens."""
    a, b, c = one_slot.prompts
    eng = one_slot.make()
    outs = serve_all(eng, [a, b, c], 4)
    assert outs == [serve_all(one_slot.make(), [p], 4)[0] for p in (a, b, c)]
    stats = eng.stats()
    assert stats["rows_dropped"] == 0
    assert stats["steps"] == 3 * (one_slot.first + 4 - 1)
    # all but the first were dispatched with the step before unfetched
    assert stats["steps_ahead"] == stats["steps"] - 1
    # the prompt + answer that reaches max_model_len stops the same way
    long = a_prompt(eng.ecfg.max_model_len - 3, salt=4)
    long = [t % 300 for t in long]
    out, = serve_all(eng, [long], 8)
    assert len(out) == 3 and eng.stats()["rows_dropped"] == 0
    assert eng.stats()["free_blocks"] == eng.ecfg.num_kv_blocks


def test_an_abort_between_dispatch_and_fetch_drops_the_row_in_flight(
        one_slot):
    """A consumer that walks away mid-decode: the sweep releases the slot
    while a step that carries a row of the sequence is in flight; that row
    is dropped at the fetch and the request admitted into the slot meanwhile
    gets its own tokens."""
    a, b, _ = one_slot.prompts
    plain_b, = serve_all(one_slot.make(), [b], 6)
    eng = one_slot.make()

    async def main():
        gen = eng.generate_stream(a, max_tokens=30)
        head = [await gen.__anext__() for _ in range(3)]
        later = asyncio.ensure_future(serve_one(eng, b, 6))
        await gen.aclose()
        return head, await later

    async def serve_one(eng, p, n):
        return [t async for t in eng.generate_stream(p, max_tokens=n)]

    head, got_b = asyncio.run(main())
    assert len(head) == 3 and got_b == plain_b
    stats = eng.stats()
    assert stats["rows_dropped"] >= 1
    assert stats["tokens_out"] + stats["rows_dropped"] == (
        stats["steps"] + (0 if one_slot.first else 2))
    assert stats["free_blocks"] == eng.ecfg.num_kv_blocks


@pytest.mark.parametrize("donated", [False, True],
                         ids=["before_the_call", "after_the_donation"])
def test_a_step_that_raises_with_two_outstanding_fails_every_request(
        one_slot, donated):
    """The third dispatch raises while the second step's tokens are still
    on the device: both are suspect. The request in the slot, the one whose
    last step was in flight and the one queued all get the error; the next
    request is served from a clean engine."""
    a, b, c = one_slot.prompts
    eng = one_slot.make()
    step, calls = eng._decode, []

    def failing(*args):
        calls.append(len(calls))
        if len(calls) == 3:
            if donated:
                step(*args)
            raise RuntimeError("the chip fell over")
        return step(*args)

    eng._decode = failing
    # `a` ends at the dispatch of its second step (in flight at the failure),
    # `b` is in the slot, `c` waits
    outs = serve_all(eng, [a, b, c], [3 - one_slot.first, 8, 8])
    assert all(isinstance(o, RuntimeError) for o in outs), outs
    assert eng._flight is None and not eng.active.any()
    assert eng.stats()["free_blocks"] == eng.ecfg.num_kv_blocks
    eng._decode = step
    assert serve_all(eng, [c], 5) == serve_all(one_slot.make(), [c], 5)


class _RecordedToks:
    """A step's result that says when the host fetches it."""

    def __init__(self, n, value, log):
        self.n, self.value, self.log = n, value, log

    def __array__(self, dtype=None, copy=None):
        self.log.append(("fetch", self.n))
        return np.asarray(self.value)


def test_a_recording_step_set_sees_each_dispatch_before_the_fetch_ahead_of_it(
        toy):
    """The toy family with a decode step that records when it is dispatched
    and when its tokens are fetched: dispatch n+1 comes before fetch n, every
    step under load was dispatched ahead, and the loop goes idle with
    nothing in flight."""
    cfg, params = toy
    log = []
    inner, path, note = _toy_decode_step(cfg, TOY_ECFG)

    def recording(params, bag, *slots):
        *slots, prev, fed = slots
        log.append(("dispatch", sum(k == "dispatch" for k, _ in log) + 1))
        toks, bag = inner(params, bag, *slots, getattr(prev, "value", prev),
                          fed)
        return _RecordedToks(log[-1][1], toks, log), bag

    eng = PagedEngine(cfg, params, TOY_ECFG)
    eng._decode = recording
    at = {}

    async def main():
        gen = eng.generate_stream([5, 6, 7], max_tokens=12)
        head = [await gen.__anext__() for _ in range(3)]
        at["early"] = eng.stats()
        short = [t async for t in eng.generate_stream([9, 9], max_tokens=4)]
        at["late"] = eng.stats()
        return head + [t async for t in gen], short

    long, short = asyncio.run(main())
    assert long == toy_reference(params, [5, 6, 7], 12)
    assert short == toy_reference(params, [9, 9], 4)
    n = eng.stats()["steps"]
    assert n == 11 and eng.stats()["steps_ahead"] == n - 1
    # dispatch 1, then dispatch n+1 before fetch n, and at the end the last
    # fetch alone
    want = [("dispatch", 1)]
    for k in range(1, n):
        want += [("dispatch", k + 1), ("fetch", k)]
    assert log == want + [("fetch", n)]
    # under load every step is dispatched ahead
    steps = at["late"]["steps"] - at["early"]["steps"]
    assert steps > 0
    assert at["late"]["steps_ahead"] - at["early"]["steps_ahead"] == steps
    assert eng._flight is None and eng.stats()["rows_dropped"] == 0


@pytest.mark.parametrize("slow", ["fetch", "dispatch"])
def test_a_turn_is_unwaited_where_its_tokens_were_ready_at_the_fetch(
        toy, monkeypatch, slow):
    """The toy family with a decode step whose tokens take a while to fetch
    (a device that sets the pace: every turn waits) or whose dispatch takes
    a while and returns tokens that are ready (a host that does: no turn
    waits, and the unwaited turns' seconds are all of the turns'). The
    limit is raised from its millisecond so that a test machine that takes
    the thread away between two clock reads changes nothing."""
    cfg, params = toy
    inner, _, _ = _toy_decode_step(cfg, TOY_ECFG)
    nap_s = 0.03
    monkeypatch.setattr(_engine, "UNWAITED_S", nap_s / 3)

    class Toks:
        def __init__(self, value):
            self.value = value

        def __array__(self, dtype=None, copy=None):
            if slow == "fetch":
                time.sleep(nap_s)
            return self.value

    def paced(params, bag, *slots):
        *slots, prev, fed = slots
        toks, bag = inner(params, bag, *slots, getattr(prev, "value", prev),
                          fed)
        value = np.asarray(toks)          # the step has run
        if slow == "dispatch":
            time.sleep(nap_s)
        return Toks(value), bag

    eng = PagedEngine(cfg, params, TOY_ECFG)
    eng._decode = paced
    assert serve_all(eng, [[5, 6, 7]], 8) == [toy_reference(
        params, [5, 6, 7], 8)]
    s = eng.stats()
    assert s["steps"] == s["steps_w0"] >= 7
    assert s["turn_s_w0"] == s["loop_turn_s"] >= (s["steps"] - 1) * nap_s
    if slow == "fetch":
        assert s["turns_unwaited"] == 0 and s["turn_unwaited_s"] == 0.0
        assert s["steps"] * nap_s <= s["loop_wait_s"] <= s["loop_turn_s"]
    else:
        assert s["turns_unwaited"] == s["steps"]
        assert s["turn_unwaited_s"] == s["loop_turn_s"]
        assert s["loop_wait_s"] < s["steps"] * nap_s / 3


def test_a_prompts_last_chunk_and_first_decode_row_ride_consecutive_steps(
        chunk_params):
    """Mixed admissions beside a decoding slot, greedy and seeded: the step
    after the one that carried a prompt's last chunk already decodes the
    slot, from the first token and the stream key that step left on the
    device (no request loses a step to the lookahead), and the streams are
    the reference's token for token."""
    eng = PagedEngine(CFG, chunk_params, CHUNK_ECFG)
    step, seen = eng._decode, []

    def recording(width, params, kc, vc, tables, lens, active, last_tok,
                  keys, temps, prev, fed, *chunk):
        seen.append((width, np.asarray(active), np.asarray(fed),
                     np.asarray(chunk[1]) if chunk else None))
        return step(width, params, kc, vc, tables, lens, active, last_tok,
                    keys, temps, prev, fed, *chunk)

    eng._decode = recording
    prompts = [a_prompt(n, salt=9) for n in (2 * 16 + 3, 7, 19)]

    async def one(p, temperature):
        rid = eng._rid + 1
        return rid, [t async for t in eng.generate_stream(
            p, max_tokens=ANSWER, temperature=temperature, seed=SEED)]

    async def main():
        gen = eng.generate_stream(a_prompt(6, salt=8), max_tokens=40)
        head = [await gen.__anext__() for _ in range(2)]
        outs = await asyncio.gather(
            one(prompts[0], 0.0), one(prompts[1], TEMPERATURE),
            one(prompts[2], 0.0))
        return head + [t async for t in gen], outs

    background, outs = asyncio.run(main())
    assert [background] == greedy_reference(
        chunk_params, [a_prompt(6, salt=8)], 40)
    assert [outs[0][1], outs[2][1]] == greedy_reference(
        chunk_params, [prompts[0], prompts[2]], ANSWER)
    rid, toks = outs[1]
    assert toks == seeded_reference(chunk_params, prompts[1], rid, len(toks))
    # every chunk that ended a prompt: its slot decodes in the next step
    ends = 0
    for (width, _, _, at), (_, active, fed, _) in zip(seen, seen[1:]):
        if width and any(at[1] + at[2] == len(p) for p in prompts):
            ends += 1
            assert active[at[0]] and fed[at[0]] == _engine.FED_CHUNK
    assert ends == 3
    stats = eng.stats()
    assert stats["steps"] == 40 and stats["rows_dropped"] == 0
    assert stats["steps_ahead"] == 39


# ---------------------------------------------------------------------------
# which admitted prompt a step's one chunk goes to
# ---------------------------------------------------------------------------

# widest chunk 16 at a length of 127: a prompt of up to eight chunks
ORDER_ECFG = EngineConfig(max_num_seqs=5, kv_block_size=4, num_kv_blocks=160,
                          max_model_len=127, prefix_cache=False)
WIDEST = 16


def chunks_of(n):
    return -(-n // WIDEST)


def order_engine(chunk_params):
    """An engine that notes every dispatched step: (slot, start, tokens) of
    the chunk it carries, or None."""
    eng = PagedEngine(CFG, chunk_params, ORDER_ECFG)
    step, seen = eng._decode, []

    def recording(width, *args):
        seen.append(tuple(int(x) for x in np.asarray(args[-1]))
                    if width else None)
        return step(width, *args)

    eng._decode = recording
    return eng, seen


async def first_token(eng, prompt):
    return [t async for t in eng.generate_stream(prompt, max_tokens=1)]


PASSED = (115, 5, 20, 9)


@pytest.fixture(scope="module")
def passed(chunk_params):
    """One prompt of eight chunks and three of one, two and one admitted
    behind it in the same turn, a token each: (the tokens, which prompt each
    chunk step went to, the engine's stats). Admitted in list order, so
    prompt i holds slot i."""
    eng, seen = order_engine(chunk_params)
    prompts = [a_prompt(n, salt=21) for n in PASSED]
    outs = serve_all(eng, prompts, 1)
    assert outs == greedy_reference(chunk_params, prompts, 1)
    return [at[0] for at in seen], eng.stats()


def test_short_prompts_pass_a_long_one_which_runs_on_every_second_chunk_step(
        passed):
    """The chunk steps alternate between the oldest and the one with the
    least left, so each short prompt's last chunk runs within twice the
    chunks of itself and of the shorter ones ahead of it, and the long one
    runs on every second chunk step until they are through."""
    order, stats = passed
    assert order == [0, 1, 0, 3, 0, 2, 0, 2, 0, 0, 0, 0]
    for i in (1, 2, 3):
        ahead = sum(chunks_of(n) for j, n in enumerate(PASSED)
                    if j and (n, j) <= (PASSED[i], i))
        assert max(k for k, who in enumerate(order, 1) if who == i) <= 2 * ahead
    # never two chunk steps in a row without the oldest
    assert all(0 in pair for pair in zip(order, order[1:]))
    assert stats["prefill_chunks"] == len(order) == stats["steps"]


def test_chunk_overtakes_counts_the_chunk_steps_not_given_to_the_oldest(
        passed):
    order, stats = passed
    left = [chunks_of(n) for n in PASSED]
    overtakes = 0
    for who in order:
        overtakes += who != next(i for i, n in enumerate(left) if n)
        left[who] -= 1
    assert not any(left)
    assert stats["chunk_overtakes"] == overtakes == 4
    assert stats["steps_with_chunk"] == stats["prefill_chunks"] == 12


def test_a_long_prompt_finishes_under_an_endless_supply_of_short_ones(
        chunk_params):
    """Three callers send one-chunk prompts back to back for as long as the
    long prompt is in chunks: it still gets every second chunk step, and its
    first token comes within twice its own chunks plus one."""
    eng, seen = order_engine(chunk_params)
    long = a_prompt(120, salt=22)
    done = asyncio.Event()

    async def caller(i):
        k = 0
        while not done.is_set():
            k += 1
            await first_token(eng, a_prompt(3 + (i + k) % 9, salt=100 * i + k))
        return k

    async def main():
        eng._pending = eng._loop_task = None
        first = asyncio.ensure_future(first_token(eng, long))
        # the long prompt is the oldest: admitted before any short one
        while not eng._prefilling:
            await asyncio.sleep(0)
        callers = [asyncio.ensure_future(caller(i)) for i in range(3)]
        tok = await first
        at = len(seen)
        done.set()
        return tok, at, sum(await asyncio.gather(*callers))

    tok, at, sent = asyncio.run(main())
    assert [tok] == greedy_reference(chunk_params, [long], 1)
    assert sent > chunks_of(len(long)) // 2
    # admitted first, so in slot 0: all its chunks within the bound
    assert sum(1 for c in seen[:at] if c and c[0] == 0) == chunks_of(len(long))
    assert at <= 2 * chunks_of(len(long)) + 1
    stats = eng.stats()
    assert 0 < stats["chunk_overtakes"] <= stats["prefill_chunks"] // 2 + 1


@pytest.mark.parametrize("beside", [0, 2], ids=["idle", "beside_two_decoding"])
def test_one_prompt_in_chunks_runs_as_it_always_did(chunk_params, beside):
    """With a single request in chunks both choices name it: consecutive
    steps carry its chunks in order, the widest until the last, as when the
    oldest took every chunk step; nobody is overtaken."""
    eng, seen = order_engine(chunk_params)
    long = a_prompt(115, salt=23)

    async def main():
        eng._pending = eng._loop_task = None
        gens = [eng.generate_stream(a_prompt(5, salt=30 + i), max_tokens=40)
                for i in range(beside)]
        for g in gens:
            await g.__anext__()
        while eng._prefilling:
            await asyncio.sleep(0)
        at = len(seen)
        tok = await first_token(eng, long)
        for g in gens:
            await g.aclose()
        return at, tok

    at, tok = asyncio.run(main())
    assert [tok] == greedy_reference(chunk_params, [long], 1)
    slot = beside
    want = [(slot, 16 * i, min(16, 115 - 16 * i)) for i in range(8)]
    assert seen[at:at + 8] == want
    assert eng.stats()["chunk_overtakes"] == 0


@pytest.mark.parametrize("b_len", [90, 40], ids=["not_started", "half_run"])
def test_an_aborted_request_in_the_middle_of_the_queue_is_skipped(
        chunk_params, b_len):
    """Three prompts in chunks and the caller of the middle one walks away,
    before its first chunk (it is neither the oldest nor the shortest) or
    after it (it is the shortest): the sweep releases its slot and blocks
    once, the scheduler passes over it wherever it stands, and the other two
    get the reference's tokens."""
    eng, seen = order_engine(chunk_params)
    a, b, c = (a_prompt(n, salt=24) for n in (120, b_len, 60))

    async def main():
        eng._pending = eng._loop_task = None
        first = asyncio.ensure_future(first_token(eng, a))
        while not eng._prefilling:
            await asyncio.sleep(0)
        gone = asyncio.ensure_future(first_token(eng, b))
        last = asyncio.ensure_future(first_token(eng, c))
        # until the shortest of the three has had a chunk
        while not any(at and at[0] == (1 if b_len == 40 else 2) for at in seen):
            await asyncio.sleep(0)
        assert [len(r.prompt) for r in eng._prefilling] == [120, b_len, 60]
        gone.cancel()
        return await first, await last

    outs = asyncio.run(main())
    assert list(outs) == greedy_reference(chunk_params, [a, c], 1)
    run = [sum(n for slot, _, n in filter(None, seen) if slot == k)
           for k in range(3)]
    assert run[0] == 120 and run[2] == 60
    assert run[1] == 0 if b_len == 90 else 0 < run[1] < 40
    assert not eng._prefilling and eng.slot_req == [None] * 5
    assert sorted(eng.free_blocks) == list(range(1, 161))

"""Prefix-cache correctness + KV-block lifecycle (reference: vLLM automatic
prefix caching tests): pure PrefixCache units, warm-vs-cold generation
equality through the paged engine's chunks that start behind the cached
blocks, blocks shared while their prompt is still in chunks, and the
client-disconnect block-leak regression."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.llm._prefix_cache import PrefixCache, chain_keys
from ray_tpu.models.llama import LlamaConfig, init_params

CFG = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=256, dtype=jnp.float32, param_dtype=jnp.float32)


def _engine(**over):
    params = init_params(CFG, jax.random.PRNGKey(0))
    kw = dict(max_num_seqs=2, kv_block_size=16, num_kv_blocks=32,
              max_model_len=256, prefix_cache=True)
    kw.update(over)
    return PagedEngine(CFG, params, EngineConfig(**kw))


# -- pure host-side cache ---------------------------------------------------


def test_chain_keys_commit_to_whole_prefix():
    keys = chain_keys(list(range(40)), block_size=16)
    assert len(keys) == 2  # only FULL blocks get keys
    # same prefix -> same chain; a changed FIRST block changes every key
    assert chain_keys(list(range(40)), 16) == keys
    other = chain_keys([99] + list(range(1, 40)), 16)
    assert other[0] != keys[0] and other[1] != keys[1]
    # shared first block, divergent second: chain splits at the change
    fork = chain_keys(list(range(16)) + [7] * 16, 16)
    assert fork[0] == keys[0] and fork[1] != keys[1]


def test_match_increfs_and_cancel_returns():
    c = PrefixCache(block_size=4)
    keys = chain_keys([1, 2, 3, 4, 5, 6, 7, 8], 4)
    assert c.register(keys, [10, 11]) == []
    # the registering request holds one ref per block
    assert c.evictable_blocks() == 0
    assert c.decref_block(10) and c.decref_block(11)
    assert c.evictable_blocks() == 2
    got = c.match(keys)
    assert got == [10, 11] and c.evictable_blocks() == 0
    c.cancel_match(got)
    assert c.evictable_blocks() == 2
    # longest-prefix semantics: an unknown tail matches only the known head
    longer = chain_keys([1, 2, 3, 4, 5, 6, 7, 8, 9, 9, 9, 9], 4)
    got = c.match(longer)
    assert got == [10, 11]
    c.cancel_match(got)


def test_eviction_keeps_refcounted_blocks():
    """Eviction may only reclaim zero-ref entries — a block an admitted
    request still holds must survive any eviction pressure."""
    c = PrefixCache(block_size=4)
    busy = chain_keys([1, 1, 1, 1], 4)
    idle = chain_keys([2, 2, 2, 2], 4)
    c.register(busy, [5])          # refs=1: an active request holds it
    c.register(idle, [6])
    c.decref_block(6)              # idle entry: refs=0, evictable
    freed = c.evict(10)
    assert freed == [6]            # only the zero-ref block came back
    assert c.owns_block(5) and not c.owns_block(6)
    # once the holder releases, the survivor becomes reclaimable too
    c.decref_block(5)
    assert c.evict(10) == [5]


def test_eviction_is_leaf_first():
    c = PrefixCache(block_size=4)
    keys = chain_keys(list(range(12)), 4)  # 3-block chain
    c.register(keys, [7, 8, 9])
    for b in (7, 8, 9):
        c.decref_block(b)
    # one block wanted: the LEAF (deepest chain entry) goes first, so the
    # remaining chain stays internally reachable
    assert c.evict(1) == [9]
    assert c.match(keys) == [7, 8]
    c.cancel_match([7, 8])


def test_register_cap_evicts_lru():
    c = PrefixCache(block_size=4, max_entries=2)
    a = chain_keys([1, 1, 1, 1], 4)
    b = chain_keys([2, 2, 2, 2], 4)
    d = chain_keys([3, 3, 3, 3], 4)
    c.register(a, [10]); c.decref_block(10)
    c.register(b, [11]); c.decref_block(11)
    got = c.match(b); c.cancel_match(got)      # touch b: a is now LRU
    evicted = c.register(d, [12])
    assert evicted == [10]                     # cap held by evicting LRU a
    assert c.owns_block(11) and c.owns_block(12)


# -- engine integration -----------------------------------------------------


def _gen(eng, prompt, max_tokens=8):
    async def run():
        return [t async for t in eng.generate_stream(
            prompt, max_tokens=max_tokens, temperature=0.0)]

    return asyncio.run(run())


def test_warm_generation_matches_cold_byte_identical():
    """The tentpole correctness bar: a prompt served from cached prefix
    blocks produces EXACTLY the cold tokens, and the hit counters prove
    the warm path actually ran."""
    eng = _engine()
    prefix = list(np.random.RandomState(0).randint(1, 500, size=80))

    async def main():
        cold = [t async for t in eng.generate_stream(
            prefix + [7, 8, 9], max_tokens=8, temperature=0.0)]
        s1 = eng.stats()["prefix_cache"]
        warm = [t async for t in eng.generate_stream(
            prefix + [7, 8, 9], max_tokens=8, temperature=0.0)]
        s2 = eng.stats()["prefix_cache"]
        return cold, warm, s1, s2

    cold, warm, s1, s2 = asyncio.run(main())
    assert warm == cold
    assert s2["block_hits"] > s1["block_hits"]
    assert s2["hits"] >= 1
    # pool accounting stays exact: cached blocks are free capacity
    st = eng.stats()
    assert st["free_blocks"] == 32 and st["blocks_in_use"] == 0


def test_shared_prefix_different_tail_reuses_blocks():
    eng = _engine()
    prefix = list(np.random.RandomState(1).randint(1, 500, size=64))
    a = _gen(eng, prefix + [7, 8, 9])
    hits0 = eng.stats()["prefix_cache"]["block_hits"]
    b = _gen(eng, prefix + [11, 12, 13])
    assert eng.stats()["prefix_cache"]["block_hits"] > hits0
    assert len(a) == 8 and len(b) == 8
    # divergent tails must not alias: rerun both cold for ground truth
    cold = _engine(prefix_cache=False)
    assert _gen(cold, prefix + [7, 8, 9]) == a
    assert _gen(cold, prefix + [11, 12, 13]) == b


def test_cache_disabled_engine_unaffected():
    eng = _engine(prefix_cache=False)
    prefix = [3] * 40
    assert _gen(eng, prefix) == _gen(eng, prefix)
    st = eng.stats()
    assert st["prefix_cache"] is None
    assert st["free_blocks"] == 32


def test_eviction_under_pool_pressure_preserves_output():
    """A pool too small for all cached prefixes forces admission-time
    eviction; results stay correct and the pool never leaks."""
    eng = _engine(num_kv_blocks=16, max_num_seqs=1)
    outs = {}
    for seed in range(4):
        p = list(np.random.RandomState(seed).randint(1, 500, size=64))
        outs[seed] = _gen(eng, p, max_tokens=4)
    assert eng.stats()["prefix_cache"]["evictions"] > 0
    st = eng.stats()
    assert st["free_blocks"] == 16 and st["blocks_in_use"] == 0
    # warm rerun of the LAST prompt (its blocks are still resident)
    p = list(np.random.RandomState(3).randint(1, 500, size=64))
    assert _gen(eng, p, max_tokens=4) == outs[3]


# -- chunks over cached blocks ------------------------------------------------
# max_model_len 256: the ladder of chunk widths is (32, 64)


def _dense(prompt, max_tokens=8):
    from ray_tpu.llm._generate import generate

    return generate(CFG, init_params(CFG, jax.random.PRNGKey(0)), [prompt],
                    max_new_tokens=max_tokens, temperature=0.0)[0]


@pytest.mark.parametrize("tail", [1, 15, 16, 17, 64, 65, 2 * 64 + 3])
def test_chunks_start_at_the_cached_length(tail):
    """A prompt whose first 80 tokens (5 blocks) are cached runs only its
    tail, as chunks whose first starts at position 80: on and around the
    chunk widths and over several chunks. The answer is the dense
    decoder's, and the chunks held exactly the tokens the cache did not."""
    eng = _engine()
    prefix = [int(t) for t in np.random.RandomState(5).randint(1, 500, 80)]
    _gen(eng, prefix + [3], max_tokens=2)
    before = eng.stats()
    prompt = prefix + [int(t) for t in
                       np.random.RandomState(tail).randint(1, 500, tail)]
    assert _gen(eng, prompt) == _dense(prompt)
    after = eng.stats()
    assert (after["prefix_cache"]["block_hits"]
            - before["prefix_cache"]["block_hits"]) == 5
    assert (after["prefill_chunk_tokens"]
            - before["prefill_chunk_tokens"]) == tail
    assert (after["prefill_chunks"] - before["prefill_chunks"]
            == -(-tail // 64))


def test_a_prompt_still_in_chunks_shares_only_the_blocks_written():
    """The second request shares 230 tokens with the first and arrives when
    the first has run one chunk of its four: it is matched to blocks a
    finished step wrote (64 tokens a chunk, 4 blocks) and to none of the
    14 that share its keys but hold nothing yet; both answers are the
    dense decoder's."""
    eng = _engine(max_num_seqs=3)
    shared = [int(t) for t in np.random.RandomState(9).randint(1, 500, 230)]
    first, second = shared + [7, 8, 9], shared + [11, 12]

    async def one(prompt):
        return [t async for t in eng.generate_stream(
            prompt, max_tokens=8, temperature=0.0)]

    async def main():
        a = asyncio.ensure_future(one(first))
        while eng.stats()["prefill_chunks"] < 1:
            await asyncio.sleep(0)
        hits0 = eng.stats()["prefix_cache"]["block_hits"]
        b = asyncio.ensure_future(one(second))
        while eng.stats()["prefix_cache"]["block_hits"] == hits0:
            await asyncio.sleep(0)
        hits = eng.stats()["prefix_cache"]["block_hits"] - hits0
        return await a, await b, hits

    a, b, hits = asyncio.run(main())
    assert hits % 4 == 0 and 4 <= hits < 230 // 16, hits
    assert a == _dense(first) and b == _dense(second)
    st = eng.stats()
    # each ran what it was not handed: the second from its hits on
    assert st["prefill_chunk_tokens"] == len(first) + len(second) - 16 * hits
    assert st["free_blocks"] == 32 and st["blocks_in_use"] == 0


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_an_abort_between_two_chunks_hands_every_block_back(prefix_cache):
    """The consumer of a four-chunk prompt leaves after its first chunk: the
    next sweep frees the slot and every block, the blocks the chunk had
    completed stay cached (evictable), and the next request is served."""
    eng = _engine(prefix_cache=prefix_cache)
    prompt = [int(t) for t in np.random.RandomState(4).randint(1, 500, 200)]

    async def main():
        gen = eng.generate_stream(prompt, max_tokens=8)
        waiter = asyncio.ensure_future(gen.__anext__())
        while eng.stats()["prefill_chunks"] < 1:
            await asyncio.sleep(0)
        waiter.cancel()
        await asyncio.gather(waiter, return_exceptions=True)
        await gen.aclose()
        for _ in range(100):
            await asyncio.sleep(0.02)
            if eng.stats()["blocks_in_use"] == 0:
                break
        return eng.stats()

    st = asyncio.run(main())
    assert st["prefill_chunks"] < 4 and st["tokens_out"] == 0, st
    assert st["blocks_in_use"] == 0 and st["free_blocks"] == 32
    assert st["active_slots"] == 0
    assert _gen(eng, prompt[:90]) == _dense(prompt[:90])


# -- client-disconnect leak regression --------------------------------------


@pytest.mark.parametrize("prefix_cache", [False, True])
def test_aborted_streams_leak_no_blocks(prefix_cache):
    """N clients take one token and walk away: the engine's abort sweep
    must return every KV block — with the cache ON, held refs drop so the
    blocks become evictable capacity; OFF, they return to the free list."""
    eng = _engine(prefix_cache=prefix_cache, max_num_seqs=2)
    prefix = list(np.random.RandomState(2).randint(1, 500, size=48))

    async def main():
        async def aborted(i):
            gen = eng.generate_stream(prefix + [i], max_tokens=64)
            async for _ in gen:
                break  # one token, then disconnect
            await gen.aclose()

        for i in range(6):
            await aborted(i)
        # the sweep runs on the engine loop: give it a few ticks
        for _ in range(100):
            await asyncio.sleep(0.02)
            st = eng.stats()
            if st["blocks_in_use"] == 0 and st["active_slots"] == 0:
                break
        return eng.stats()

    st = asyncio.run(main())
    assert st["blocks_in_use"] == 0, st
    assert st["active_slots"] == 0
    assert st["free_blocks"] == 32
    # an aborted request's waiting twin admitted later still completes
    assert len(_gen(eng, prefix + [99], max_tokens=4)) == 4

"""Prefix-cache correctness + KV-block lifecycle (reference: vLLM automatic
prefix caching tests): pure PrefixCache units, warm-vs-cold generation
equality through the paged engine's chunks that start behind the cached
blocks, blocks shared while their prompt is still in chunks, and the
client-disconnect block-leak regression."""

import asyncio
import itertools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.llm._prefix_cache import (
    PrefixCache, SnapshotsAtChunks, SnapshotsAtMatch, chain_keys)
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
    c.register(keys, [10, 11])
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


def _idle_chain(c, seed, n, first_block, bs=4):
    """Chain `seed` of `n` blocks, cached in blocks first_block.., released."""
    keys = chain_keys([seed] * (n * bs), bs)
    blocks = list(range(first_block, first_block + n))
    c.register(keys, blocks)
    for b in blocks:
        c.decref_block(b)
    return keys, blocks


def _touch(c, keys):
    c.cancel_match(c.match(keys))


@pytest.mark.parametrize("want", [3, 5, 6])
def test_the_oldest_idle_chain_goes_whole_before_the_next_loses_a_block(want):
    """Three idle chains; their ages are their last uses, not the order they
    came in. Up to the oldest's length only the oldest loses blocks, tail
    first; one more takes exactly one leaf of the second oldest."""
    c = PrefixCache(block_size=4)
    a, a_blocks = _idle_chain(c, 1, 4, 10)
    b, b_blocks = _idle_chain(c, 2, 5, 20)
    d, d_blocks = _idle_chain(c, 3, 3, 30)
    for keys in (b, d, a):          # b is now the oldest, then d, then a
        _touch(c, keys)
    order = b_blocks[::-1] + d_blocks[::-1] + a_blocks[::-1]
    assert c.evict(want) == order[:want]
    left = 5 - min(want, 5)
    got = c.match(b)
    assert got == b_blocks[:left]
    c.cancel_match(got)
    assert len(c.match(d)) == (3 if want <= 5 else 2)
    assert c.match(a) == a_blocks
    # each leaf was filed at its first release: it surfaces once at that age
    # and is filed again at its own
    assert c.stats()["evict_examined"] == want + 3 and len(c) == 12 - want


@pytest.mark.parametrize("want", [4, 8])
def test_a_forks_tails_go_before_the_block_they_share(want):
    """One document, two question tails, and a younger chain beside them:
    both tails go (the older first, each tail first) before any shared
    block, and the shared blocks follow in the same call once the second
    tail has; the younger chain is not touched."""
    c = PrefixCache(block_size=4)
    doc = [7] * 16
    q1 = chain_keys(doc + [1] * 8, 4)
    q2 = chain_keys(doc + [2] * 8, 4)
    assert q1[:4] == q2[:4] and q1[4] != q2[4]
    c.register(q1, [0, 1, 2, 3, 4, 5])
    got = c.match(q2)
    assert got == [0, 1, 2, 3]
    c.register(q2, got + [6, 7])
    for b in [0, 1, 2, 3, 4, 5] + [0, 1, 2, 3, 6, 7]:
        c.decref_block(b)
    young, young_blocks = _idle_chain(c, 9, 3, 20)
    assert c.evictable_blocks() == 11
    assert c.evict(want) == [5, 4, 7, 6, 3, 2, 1, 0][:want]
    assert len(c.match(q1)) == (4 if want == 4 else 0)
    assert c.match(young) == young_blocks


def test_a_chain_taken_back_is_never_freed_and_ages_from_its_release():
    c = PrefixCache(block_size=4)
    a, a_blocks = _idle_chain(c, 1, 3, 10)
    b, b_blocks = _idle_chain(c, 2, 3, 20)
    held = c.match(a)               # a, the older, is in use again
    assert c.evict(1) == [22]       # its stale item is passed over
    assert c.stats()["evict_examined"] == 2
    c.cancel_match(held)            # idle again, younger than b now
    assert c.evict(1) == [21]
    held = c.match(a)
    assert c.evict(10) == [20]      # all there is while a is held
    assert c.evictable_blocks() == 0 and len(c) == 3
    c.cancel_match(held)
    assert c.evict(10) == a_blocks[::-1] and len(c) == 0


def test_an_eviction_costs_the_blocks_it_frees_not_the_cache():
    """40 idle chains of 800 blocks, a cache of 32,000 entries: freeing
    1,024 looks at no more than twice that many heap items (at them and no
    others: there is no pass over the entries), the oldest chain whole and
    224 from the tail of the next."""
    c = PrefixCache(block_size=2)
    chains = [_idle_chain(c, seed, 800, 800 * seed, bs=2)
              for seed in range(40)]
    assert len(c) == c.evictable_blocks() == 32_000
    freed = c.evict(1024)
    s = c.stats()
    assert s["evict_calls"] == 1 and s["evictions"] == 1024
    assert 1024 <= s["evict_examined"] <= 2 * 1024
    assert len(c) == c.evictable_blocks() == 32_000 - 1024
    assert freed == chains[0][1][::-1] + chains[1][1][:-225:-1]


def test_chain_keys_hash_the_ids_whatever_holds_them():
    ids = [int(t) for t in np.random.RandomState(0).randint(0, 200_000, 70)]
    keys = chain_keys(ids, 16)
    assert len(keys) == 4 and len(set(keys)) == 4
    assert all(len(k) == 16 for k in keys)
    for same in (tuple(ids), np.asarray(ids, np.int32),
                 np.asarray(ids, np.int64)):
        assert chain_keys(same, 16) == keys
    assert chain_keys(ids[:63], 16) == keys[:3]
    assert chain_keys(ids[:15], 16) == []
    for at in (0, 17, 40, 63):      # one id differs: every key from its block
        other = list(ids)
        other[at] += 1
        got = chain_keys(other, 16)
        first = at // 16
        assert got[:first] == keys[:first]
        assert all(g != k for g, k in zip(got[first:], keys[first:]))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_idle_count_and_the_leaf_rule_hold_over_random_traffic(seed):
    """A few hundred admissions (match, take blocks, register), releases,
    cancelled matches and evictions over prompts that share prefixes: the
    kept count of idle blocks is the count of zero-reference entries after
    every one, and every evicted block was idle and childless when it went.
    """
    rng = np.random.RandomState(seed)
    bs = 2
    c = PrefixCache(block_size=bs)
    free = list(range(40))
    live = []                       # blocks of the requests in flight

    def check():
        assert c.evictable_blocks() == sum(
            e.refs == 0 for e in c._entries.values())

    def evict(want):
        refs = {e.block: e.refs for e in c._entries.values()}
        kids = {}
        for k, e in c._entries.items():
            kids.setdefault(e.parent, set()).add(e.block)
        key_of = dict((e.block, k) for k, e in c._entries.items())
        parent_of = {e.block: e.parent for e in c._entries.values()}
        idle = c.evictable_blocks()
        freed = c.evict(want)
        # every idle block is within reach: none is left filed nowhere
        assert len(freed) == min(want, idle) == len(set(freed))
        for b in freed:
            assert refs[b] == 0 and not kids.get(key_of[b])
            kids[parent_of[b]].discard(b)
        free.extend(freed)

    for _ in range(400):
        op = rng.randint(5)
        if op <= 1:                 # admit: a document, a question, a tail
            prompt = ([int(rng.randint(4))] * (2 * bs * rng.randint(1, 5))
                      + [10 + int(rng.randint(3))] * (bs * rng.randint(3))
                      + [int(rng.randint(100))] * rng.randint(bs))
            keys = chain_keys(prompt, bs)
            hits = c.match(keys)
            need = len(keys) + 1 - len(hits)
            if len(free) < need:
                evict(need - len(free))
            if len(free) < need:
                c.cancel_match(hits)
            else:
                blocks = hits + [free.pop() for _ in range(need)]
                c.register(keys, blocks)
                live.append(blocks)
        elif op == 2 and live:      # a request ends
            for b in live.pop(rng.randint(len(live))):
                if not c.decref_block(b):
                    free.append(b)
        elif op == 3:               # an admission that found no room
            c.cancel_match(c.match(chain_keys(
                [int(rng.randint(4))] * (2 * bs * rng.randint(1, 5)), bs)))
        elif op == 4:
            evict(int(rng.randint(1, 12)))
        check()
        assert len(free) + len({b for r in live for b in r}
                               | set(c._by_block)) == 40
    for blocks in live:
        for b in blocks:
            c.decref_block(b)
    evict(40)
    assert len(c) == 0 and c.stats()["evictable"] == 0


# -- the snapshot policies, led as the engine's loop leads them --------------

_fresh_block = itertools.count(1)


def _life(policy, rid, prompt):
    """One prompt's life under `policy`, with no engine: the questions in
    the loop's order (does it wait, where does it resume, where is a chunk
    cut, is a snapshot taken at its end, which are kept) and the loop's
    calls into the cache between them. Returns (resume, restore, take_at)
    as the admission had them and the chunks' ends."""
    cache, bs = policy.cache, policy.bs
    keys = chain_keys(prompt, bs)
    assert not policy.waits(keys, ())
    hits = cache.match(keys[: (len(prompt) - 1) // bs])
    admitted = policy.resume(keys, len(hits)) if hits else (0, -1, 0)
    resume, restore, take_at = admitted
    if restore >= 0:
        cache.pin_snapshot(restore)
    blocks = hits + [next(_fresh_block) for _ in keys[len(hits):]]
    req = types.SimpleNamespace(rid=rid, slot=0, cursor=resume, kept=None,
                                block_keys=tuple(keys), take_at=take_at)
    ends = []
    while req.cursor < len(prompt):
        n = policy.cut(req, min(len(prompt) - req.cursor, policy.widest))
        take = policy.take(req, req.cursor + n)
        if restore >= 0:
            cache.pin_snapshot(restore, False)
            restore = -1
        req.cursor += n
        ends.append(req.cursor)
        full = req.cursor // bs
        cache.register(req.block_keys[:full], blocks[:full])
        if take >= 0 and cache.attach_snapshot(
                req.block_keys[full - 1], take, rid):
            policy.attached(req, req.block_keys[full - 1])
    for b in blocks:
        cache.decref_block(b)
    return admitted, ends


def _snapshots_at(cache, prompt):
    return [i * cache.block_size
            for i, k in enumerate(chain_keys(prompt, cache.block_size), 1)
            if cache.has_snapshot(k)]


def test_snapshots_at_chunks_lie_on_the_widest_chunks_multiples_two_deep_and_a_far_one_kept():
    rng = np.random.RandomState(0)
    cache = PrefixCache(block_size=16, num_snapshots=6)
    policy = SnapshotsAtChunks(cache, widest=64)
    doc = [int(t) for t in rng.randint(0, 500, 700)]
    q1, q2 = doc + [501] * 20, doc + [502] * 25
    admitted, ends = _life(policy, 1, q1)
    assert admitted == (0, -1, 0)
    # chunks of the widest width and the rest: nothing ends a chunk early
    assert ends == [*range(64, 720, 64), 720]
    # eleven taken, one a multiple of 64 (720 is none); kept: the deepest on
    # a multiple of 8 x 64, and the two deepest
    assert cache.snapshots_taken == 11 and cache.snapshots_evicted == 0
    assert _snapshots_at(cache, q1) == [512, 640, 704]
    assert len(cache._free_snaps) == 3
    # the second question shares 43 blocks (688) and resumes at the deepest
    # snapshot before their end
    (resume, restore, take_at), ends = _life(policy, 2, q2)
    assert (resume, take_at) == (640, 0) and restore >= 0
    assert cache.snapshot_owner(restore) == 1
    assert ends == [704, 725]
    assert cache.snapshots_restored == 1
    # it left its own at 704 (its block there is not the first question's)
    # and keeps it; the first's stay
    assert _snapshots_at(cache, q2) == [512, 640, 704]
    assert cache.snapshots_taken == 12 and len(cache._free_snaps) == 2


def test_snapshots_at_match_leave_one_where_prompts_part_and_a_prompt_waits_for_shared_blocks():
    rng = np.random.RandomState(1)
    cache = PrefixCache(block_size=16, num_snapshots=4)
    policy = SnapshotsAtMatch(cache, widest=64)
    header = [int(t) for t in rng.randint(0, 500, 150)]
    a, b, c = (header + [501 + i] * (21 + 6 * i) for i in range(3))
    # the first prompt behind a header leaves nothing
    assert _life(policy, 1, a) == ((0, -1, 0), [64, 128, 171])
    assert cache.snapshots_taken == 0
    # the second matches nine blocks with no snapshot near their end: it
    # runs them again, ends a chunk on the match's end and leaves one there
    assert _life(policy, 2, b) == ((0, -1, 144), [64, 128, 144, 177])
    assert cache.snapshots_taken == 1 and _snapshots_at(cache, b) == [144]
    # the third resumes from it with nothing to run again, and leaves none
    (resume, restore, take_at), ends = _life(policy, 3, c)
    assert (resume, take_at, ends) == (144, 0, [183])
    assert cache.snapshot_owner(restore) == 2
    assert cache.snapshots_taken == 1 == cache.snapshots_restored
    # a prompt behind the same header waits while one still in chunks has
    # shared blocks yet to run, and no longer; a released one is not waited
    # for, and a prompt that shares nothing does not wait
    keys = chain_keys(header + [510] * 30, 16)
    ahead = types.SimpleNamespace(slot=0, cursor=128,
                                  block_keys=tuple(chain_keys(a, 16)))
    assert policy.waits(keys, [ahead])
    ahead.cursor = 144
    assert not policy.waits(keys, [ahead])
    ahead.cursor, ahead.slot = 64, -1
    assert not policy.waits(keys, [ahead])
    ahead.slot = 0
    assert not policy.waits(chain_keys([7] * 100, 16), [ahead])
    assert not SnapshotsAtChunks(cache, 64).waits(keys, [ahead])


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

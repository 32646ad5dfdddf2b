"""A prompt whose chunks are not consecutive steps: the scheduler gives every
second chunk step to the prompt with the least left (`PagedEngine
._next_chunk`), so a half-run prompt sits out steps that carry another slot's
chunk and other slots' decode rows. Every family with a chunk ladder must
leave such a slot's memory alone. Here, at tiny sizes on the CPU: the same
prompt run alone and run between other requests leaves the same keys and
values in its blocks, the same state, tails and ring in its slot, the same
snapshots in the pool and the same first token, bit for bit.
"""

import asyncio
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import step_set
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.llm._prefix_cache import chain_keys
from ray_tpu.models import brumby, llama, mellum, solar

# what of a cache array is one request's: its blocks' rows up to the prompt's
# end, its slot's entry, the pool entries that hold its blocks' snapshots
BLOCKS, SLOT, SNAPSHOTS = "blocks", "slot", "snapshots"
# widest chunk 64 at a length of 511: a prompt of up to eight chunks
ECFG = EngineConfig(max_num_seqs=5, kv_block_size=16, num_kv_blocks=160,
                    max_model_len=511, num_state_snapshots=16)


def prompt(seed, n):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 500, n)]


HEADER = prompt(1, 136)


def family(cfg, init, layout, before, tested, chunks, snapshots=(), ecfg=ECFG,
           widest=64):
    """`before`: served one after the other before anything else (what the
    cache holds). `tested`: each run alone, and each run between other
    requests; `chunks`: how many each must run in (what the cache lets it skip
    taken off); `snapshots`: of each, (did it resume from a snapshot, how many
    the pool holds under its blocks afterwards). `widest`: the ladder's
    widest chunk under `ecfg`."""
    return types.SimpleNamespace(**locals())


FAMILIES = {
    # a document's cached blocks, then the question's chunks
    "mistral": family(
        llama.LlamaConfig(vocab_size=512, dim=64, n_layers=2, n_heads=4,
                          n_kv_heads=2, ffn_dim=128, max_seq_len=512,
                          dtype=jnp.float32, param_dtype=jnp.float32),
        llama.init_params, {"kc": BLOCKS, "vc": BLOCKS},
        before=[HEADER + prompt(2, 60)], tested=[HEADER + prompt(3, 360)],
        chunks=(6,)),
    # resumes from the document's snapshot at 128 and leaves five of its own,
    # of which it keeps three
    "solar": family(
        solar.SolarConfig.tiny(), solar.init_params,
        {"kc": BLOCKS, "vc": BLOCKS, "state": SLOT, "tails": SLOT,
         "snap_state": SNAPSHOTS, "snap_tails": SNAPSHOTS},
        before=[HEADER + prompt(2, 60)], tested=[HEADER + prompt(3, 360)],
        chunks=(6,), snapshots=((True, 3),)),
    # the second prompt behind a header runs it again and leaves the snapshot
    # where they part, the third resumes from it
    "brumby": family(
        brumby.BrumbyConfig.tiny(), brumby.init_params,
        {"state": SLOT, "norm": SLOT, "snap_state": SNAPSHOTS,
         "snap_norm": SNAPSHOTS},
        before=[HEADER + prompt(2, 60)],
        tested=[HEADER + prompt(3, 350), HEADER + prompt(4, 350)],
        chunks=(8, 6), snapshots=((False, 1), (True, 1))),
    # the cell's ring: a window of 1,024 and a widest chunk of 256, so the
    # prompt's sixth chunk starts where the ring wraps, at 1,280
    "mellum": family(
        mellum.MellumConfig.tiny(sliding_window=1024, max_seq_len=2048),
        mellum.init_params,
        {"kc": BLOCKS, "vc": BLOCKS, "wk": SLOT, "wv": SLOT},
        before=[], tested=[prompt(3, 1400)],
        ecfg=dataclasses.replace(ECFG, max_model_len=2047, num_kv_blocks=320),
        widest=256, chunks=(6,)),
}


def watch_chunks(eng):
    """Have `eng` note every chunk it dispatches: the request's prompt, its
    slot and its row of the block table."""
    inner, log = eng._chunk_at, []

    def chunk_at(req, at, n):
        if req is not None:
            log.append((tuple(req.prompt), req.slot,
                        eng.tables[req.slot].copy(), req.restore >= 0))
        return inner(req, at, n)

    eng._chunk_at = chunk_at
    return log


def memory_of(eng, layout, p, slot, row):
    """What `p`'s run left in the engine's device arrays."""
    bs, out = eng.bs, {}
    held = row[: -(-len(p) // bs)]
    keys = chain_keys(p, bs) if eng._prefix_cache is not None else []
    for name, kind in layout.items():
        a = np.asarray(getattr(eng, name))
        if kind == BLOCKS:
            mine = a[:, held]
            out[name] = mine.reshape(
                (mine.shape[0], -1) + mine.shape[3:])[:, :len(p)]
        elif kind == SLOT:
            out[name] = a[:, slot]
        else:
            for entry, key in eng._prefix_cache._snap_key.items():
                if key in keys:
                    out[name, keys.index(key)] = a[entry]
    return out


async def tokens(eng, p, n):
    return [t async for t in eng.generate_stream(p, max_tokens=n)]


def run(eng, fam, beside):
    """`before`, then every tested prompt in a round of its own: alone, or
    (`beside`) admitted ahead of three shorter prompts of five chunks in all
    while another slot decodes. A tested prompt: (first token, memory, whose
    each of the round's chunks was, did its first chunk start from a
    snapshot)."""
    log, out = watch_chunks(eng), []
    w = fam.widest

    async def main():
        eng._pending = eng._loop_task = None
        for p in fam.before:
            await tokens(eng, p, 4)
        for k, p in enumerate(fam.tested):
            others, decoding = [], None
            if beside:
                decoding = eng.generate_stream(prompt(50 + k, 10), max_tokens=60)
                for _ in range(2):
                    await decoding.__anext__()
                others = [prompt(60 + 10 * k + i, n)
                          for i, n in enumerate((w // 2, w + 6, w + 26))]
            del log[:]
            first, *_ = await asyncio.gather(
                tokens(eng, p, 1), *[tokens(eng, q, 4) for q in others])
            if decoding is not None:
                await decoding.aclose()
            while any(r is not None for r in eng.slot_req):
                await asyncio.sleep(0)
            _, slot, row, resumed = next(c for c in log if c[0] == tuple(p))
            out.append((first, memory_of(eng, fam.layout, p, slot, row),
                        [q == tuple(p) for q, *_ in log], resumed))

    asyncio.run(main())
    del eng._chunk_at
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_prompt_run_between_other_requests_leaves_what_it_leaves_alone(
        family):
    fam = FAMILIES[family]
    params = fam.init(fam.cfg, jax.random.PRNGKey(0))
    eng = PagedEngine(fam.cfg, params, fam.ecfg)
    assert step_set(fam.cfg).chunk_ladder(fam.ecfg)[-1] == fam.widest
    alone = run(eng, fam, beside=False)
    assert eng.stats()["chunk_overtakes"] == 0
    # the pool, the tables, the cache and its snapshots as at the start
    eng._reset_device_state()
    mixed = run(eng, fam, beside=True)
    overtakes = 0
    for k, ((tok_a, mem_a, order_a, res_a), (tok_b, mem_b, order_b, res_b)
            ) in enumerate(zip(alone, mixed)):
        # alone its chunks are consecutive steps; beside the others it has
        # every second chunk step at least, and the others the rest, until
        # it or they are through (the others may be admitted a turn later)
        assert order_a == [True] * fam.chunks[k]
        assert sum(order_b) == fam.chunks[k] and len(order_b) == sum(order_b) + 5
        last = len(order_b) - order_b[::-1].index(True)
        passed = order_b[:last].count(False)
        assert passed >= 3 and all(map(max, order_b, order_b[1:]))
        overtakes += passed
        assert tok_a == tok_b and len(tok_a) == 1
        assert mem_a.keys() == mem_b.keys()
        for name in mem_a:
            assert mem_a[name].any(), name
            assert np.array_equal(mem_a[name], mem_b[name]), name
        if fam.snapshots:
            resumed, left = fam.snapshots[k]
            assert res_a == res_b == resumed
            assert sum(1 for name in mem_a if name[0] == "snap_state") == left
    assert eng.stats()["chunk_overtakes"] == overtakes

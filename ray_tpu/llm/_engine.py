"""Continuous-batching LLM engine over a paged block pool — TPU-native.

Reference capability: the vLLM engine the reference wraps
(python/ray/llm/_internal/serve/engines/vllm/vllm_engine.py:283 — continuous
batching, PagedAttention block tables, streaming). Rebuilt for XLA; this
module is the scheduler, and serves any model family of
`ray_tpu.llm.MODEL_FAMILIES` through that family's *step set* (below):

- **Paged pool**: one shared pool of fixed-size blocks; each decode slot owns
  a block table (physical block ids). No per-sequence max-length allocation,
  no fragmentation: finished sequences return their blocks to the pool and a
  new request reuses them immediately. What a block holds, and what else a
  slot carries, is the step set's (`alloc_cache`); the scheduler owns the
  tables, the free list and the prefix cache over them.
- **Static shapes for XLA**: the decode step is ONE jitted function over the
  fixed slot count — inactive slots write to a reserved trash block and are
  masked out — so admission/turnover never recompiles.
- **Continuous batching, prompts in the decode step**: where the step set
  has a `chunk_ladder`, an admission is bookkeeping (slot, blocks,
  prefix-cache match); the prompt then runs as chunks of a few static
  widths, one chunk of one request a step, in the same program and the same
  weight matmuls as the running slots' tokens, so nobody waits for a
  prefill. Chunk steps alternate between the oldest prompt in chunks and the
  one with the least left to run (`_next_chunk`): a short prompt does not
  wait out every long one, and the oldest has every second chunk step at
  least. The request's first token is sampled in the step that holds its
  prompt's last token and the slot decodes from the next step on. Where the
  ladder is empty each prompt runs whole through the step set's prefill,
  awaited in the loop. The ladder is the one thing the scheduler's path
  forks on.
- **One step ahead**: a turn of the loop dispatches step n+1, then fetches
  step n's tokens, emits them, sweeps and admits, so all of the host's turn
  runs while the device computes. Step n+1 takes what step n sampled from
  the device (`feed_back`); lengths, the keys' fold and a stop by
  `max_tokens` or `max_model_len` the host applies at the dispatch; a stop
  by the end token or an abort it sees a step late, and the row that step
  computed for the ended sequence is dropped (`stats()["rows_dropped"]`).
- **Streaming**: tokens flow to callers through per-request async queues;
  the engine runs as an async actor and `generate_stream` is an async
  generator riding the framework's streaming-generator plane.

**The step set** is all `PagedEngine` knows of a model family: a namespace (a
module, or an object such as `LLAMA_STEPS` below) with exactly the names of
`STEP_SET`, found from the config's class by `ray_tpu.llm.step_set`. "cache"
is the tuple of device arrays in `CACHE_NAMES` order; B is `max_num_seqs`.

CACHE_NAMES  the device arrays every step takes after the params, donates
    and returns, each held as the engine's attribute of that name: under the
    block table [layers, num_kv_blocks + 1, kv_block_size, ...] (block 0 is
    the trash block), per slot [layers, B, ...].
    Where none lies under the block table, a block is the prefix cache's
    name for a prefix and `num_kv_blocks` sizes a list of integers:
    admission is bounded by the slots and `max_model_len`.
alloc_cache(cfg, ecfg) -> cache, zeroed.
step_params(cfg, params) -> the tree the decode step takes as `params`,
    built once at the engine's start from the tree the engine was given
    (which stays `engine.params`, under the family's published names, and is
    what every other member below takes); the given tree itself where the
    step wants nothing else. A leaf both trees hold is one buffer.
make_decode_step(cfg, ecfg) -> (step, path, note). `path` names the
    attention it was built with (`stats()["decode_attention"]`); `note` says
    why a TPU was refused the kernel, or is None. The jitted step:
        paged_decode_step([C,] params, *cache, tables [B, max_blocks],
            lens [B], active [B], last_tok [B], keys [B, 2] uint32,
            temps [B], prev [T], fed [B] [, chunk_ids [C], chunk_at [3 | 6]]
            [, probe_slot]) -> (toks [T], *cache [, probe])
    The static chunk width C leads iff the ladder is not empty, and the
    chunk (`chunk_at`: slot, start position, real tokens; with
    `SNAPSHOT_STATE` three more, below) follows iff C > 0;
    `probe_slot` (a device scalar) and `probe` are there iff `PROBE` is not
    empty. `toks` is one int32 vector of one length T whatever C, fetched
    once a step: a token a slot, then `COUNTERS`, then iff the ladder is
    not empty three more: for a chunk the token drawn from its last real
    row and the slot's decode stream key (two int32), else zeros. `prev` is
    the `toks` of the step dispatched before, still on the device, and
    `fed` says where each slot's token comes from (`FED_HOST`: `last_tok`
    and `keys` as uploaded; `FED_STEP`: `prev[slot]`; `FED_CHUNK`: the
    first token and the key behind `prev`'s tokens): the step opens with
    `feed_back`, which is what lets the loop dispatch it before the host
    has seen `prev`.
chunk_ladder(ecfg) -> the widths C > 0 the step takes, ascending; () for a
    step that takes no chunk.
make_prefill, check_prefill  a pair a step set has both of or neither
    (`WHOLE_PROMPT`): both where the ladder is empty or P/D's `PrefillWorker`
    serves the family, neither where prompts run as chunks and nothing else
    would call them (`check_routing` is then the family's check).
    make_prefill(cfg, ecfg) -> the jitted whole-prompt `paged_prefill`: with
    an empty ladder the loop's (`_admit_whole` states its signature).
    check_prefill(cfg, ecfg, prefill, params, prompt_ids) -> (got, ref):
    that prefill's last logits on caches of its own, and the family's
    reference forward pass's on the same prompt.
COUNTERS  names of what the step counts on the device; `stats()` sums them.
PROBE  keys of the dict the step returns last, fetched only while a checked
    request is in a slot (`check_routing`): "routing" (a family with routed
    experts) [layers, B, ...] of every slot (the loop's prefill returns its
    twin, [layers, S, ...],
    second; a step that carries a chunk its rows' as "chunk_routing" [layers,
    C, ...]), the others of slot `probe_slot` alone, or under "chunk_<name>"
    [layers, C, ...] of the chunk's rows.
SLOT_STATE  by name, the cache array [layers, B or more, ...] a slot carries
    beside its blocks (an admission hands it to the new request; a probed
    request reads its slot's), or None where the blocks are all a sequence
    has.
NO_PREFIX_CACHE  None where a block alone resumes a sequence, so the prefix
    cache may share it; else why not: what `prefix_cache=True` is refused
    with.
SNAPSHOT_STATE  None, or by name the cache array [num_state_snapshots + 1,
    layers, ...] that holds copies of single slots' `SLOT_STATE` (the step
    set keeps whatever else a slot carries beside it): the family's answer
    to "a block alone does not resume a sequence" that is not a refusal. The
    prefix cache then owns the pool's entries (`llm/_prefix_cache.py`), a
    match resumes at the deepest snapshot at or before its end and the
    matched tokens behind it run again, and `chunk_at` has six numbers: the
    three above, then the position from which the chunk's keys and values
    are written (what lies before it is in shared blocks and runs again for
    the state alone), the pool entry the slot's state is copied from before
    the chunk's first row (-1: none; the step starts a prompt's position 0
    from zeros), and the entry the slot's state is copied to after its last
    (the last entry, which no snapshot owns, for none).
SNAPSHOT_POLICY  None without `SNAPSHOT_STATE`; else the class (of
    `llm/_prefix_cache.py`, a `SnapshotPolicy`) that says where a prompt
    leaves snapshots and which of its own it keeps. The engine builds one
    beside its prefix cache and asks it; the why of each is with its class.
make_kv_inject(cfg, ecfg) -> the jitted, donating `paged_kv_inject(*cache,
    phys [nb], *blocks) -> cache` that seeds blocks `phys` from
    `generate_stream(prefilled=(*blocks, last_logits))`, one [layers, nb,
    ...] a cache array; raises ValueError saying why where none can.
extra_stats(cfg, cache, attn_positions_live) -> the family's own entries of
    `stats()`.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import math
import time
import types
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.models.llama import LlamaConfig, rms_norm, rope_tables
from ray_tpu.util import tracing

__all__ = ["EngineConfig", "PagedEngine", "PHASES"]

# the names a step set has, all of them and no other (module docstring),
# but for the pair it has both of or neither
STEP_SET = ("CACHE_NAMES", "alloc_cache", "step_params", "make_decode_step",
            "chunk_ladder", "make_prefill", "check_prefill", "COUNTERS",
            "PROBE", "SLOT_STATE", "NO_PREFIX_CACHE", "SNAPSHOT_STATE",
            "SNAPSHOT_POLICY", "make_kv_inject", "extra_stats")
WHOLE_PROMPT = ("make_prefill", "check_prefill")

# Host phases of the engine loop, written as `jax.profiler.TraceAnnotation`s
# into the profiler's own trace (the device trace's clock) whenever a
# profiler session is on; an inactive annotation is a flag check. Each is
# opened and closed on one thread: sweep, emit and idle on the event loop's,
# the others on the `asyncio.to_thread` worker that runs `_try_admit` or
# `_run_step`. A turn is sweep, admit*, step (upload and dispatch of the
# next step, then the wait for the one before: the device runs all through
# the turn, so the phases' times overlap its, and only the wait's end is on
# the critical path), emit. The benchmark's readers find them by these
# names.
PHASE_SWEEP = "engine:sweep"                    # drain _pending, abort sweep
PHASE_ADMIT = "engine:admit"                    # one per _try_admit call
PHASE_PREFIX_MATCH = "engine:prefix_match"      # chain_keys, match, eviction
# a step set without a chunk ladder (its prompts run whole, awaited in the
# loop), and P/D admission's first token:
PHASE_PREFILL = "engine:prefill"                # the jitted whole-prompt call
PHASE_SAMPLE_FIRST = "engine:sample_first"      # waits for the prefill
# one dispatching `_run_step` call (argument `chunk`: the width of the prompt
# chunk the step carries, 0 for none), around the three below
PHASE_STEP = "engine:step"
PHASE_UPLOAD = "engine:upload"                  # the step's host arrays
PHASE_DISPATCH = "engine:dispatch"              # the decode step's launch
# np.asarray(toks) of the step dispatched a turn earlier (argument `chunk`:
# the width of the chunk the fetched step carried, 0 for none): one a fetched
# step, alone (outside a step) when the loop drains with nothing to dispatch
PHASE_DEVICE_WAIT = "engine:device_wait"
PHASE_EMIT = "engine:emit"                      # the per-slot walk
# the wait of an engine with no request in it: in the place of a turn, so a
# device gap under it is an empty engine and one under no phase a stalled host
PHASE_IDLE = "engine:idle"
PHASES = (PHASE_SWEEP, PHASE_ADMIT, PHASE_PREFIX_MATCH, PHASE_PREFILL,
          PHASE_SAMPLE_FIRST, PHASE_STEP, PHASE_UPLOAD, PHASE_DISPATCH,
          PHASE_DEVICE_WAIT, PHASE_EMIT, PHASE_IDLE)
# the per-request spans on the tracing plane (util/tracing.py), three a
# request and none a step: the control store keeps 10,000 events
SPAN_QUEUE = "engine:queue"      # enqueue -> admission start
SPAN_PREFILL = "engine:prefill"  # admission start -> first token (its chunks)
SPAN_DECODE = "engine:decode"    # first token -> done
# a turn of the loop, from one fetch of a step's tokens to the next, is
# emit, sweep, admissions (bookkeeping, or without a chunk ladder a whole
# prompt of tens of ms), a dispatch and the rest of a decode step:
# one that takes longer than this is counted as a stall (stats())
STALL_TURN_S = 1.0
# a fetch that returns sooner than this found its tokens ready: the device
# had finished the step before the loop asked, so the host set that turn's
# pace (stats()["turns_unwaited"]). Above what the copy of ready tokens to
# the host takes (0.41-0.68 ms on a v5e, a step running behind it or none;
# microseconds on the CPU), below any wait for a step
UNWAITED_S = 1e-3


@dataclass
class EngineConfig:
    """Sizing knobs (reference: vLLM engine_kwargs max_num_seqs /
    block_size / gpu_memory_utilization → num blocks).

    They size the scheduler's side: slots, block tables, the pool's block
    count. What a block holds, and what a slot carries beside its blocks
    (sized by `max_num_seqs` alone, never paged), is the family's step set's
    `alloc_cache` (the module docstring; `LLAMA_STEPS` below,
    `llm/_ling_steps.py`)."""

    max_num_seqs: int = 4          # decode batch slots
    kv_block_size: int = 16        # tokens per KV block
    num_kv_blocks: int = 64        # pool size (excl. the trash block)
    max_model_len: int = 256       # prompt + generation cap per sequence
    # None = on, unless a block alone does not resume a sequence (the step
    # set's `NO_PREFIX_CACHE`): None then turns it off and True is refused
    prefix_cache: Optional[bool] = None
    # entries of the pool of state snapshots the prefix cache keeps for a
    # family whose slots carry a recurrent state (the step set's
    # `SNAPSHOT_STATE`; its `alloc_cache` sizes an entry); fixed for the
    # engine's life. Such a family's prefix cache needs at least two
    num_state_snapshots: int = 0


def sample_tokens(keys, logits, temps):
    """Inside a decode step: one token a slot from logits [B, V], greedy
    where the slot's temperature is 0, else drawn with the slot's key (raw
    key data [B, 2])."""
    import jax
    import jax.numpy as jnp

    def sample_one(key_data, lg, t):
        key = jax.random.wrap_key_data(key_data.astype(jnp.uint32))
        greedy = jnp.argmax(lg).astype(jnp.int32)
        samp = jax.random.categorical(
            key, lg / jnp.maximum(t, 1e-6)).astype(jnp.int32)
        return jnp.where(t > 0, samp, greedy)

    return jax.vmap(sample_one)(keys, logits, temps)


# where a slot's token comes from (a decode step's `fed`)
FED_HOST, FED_STEP, FED_CHUNK = 0, 1, 2


def feed_back(prev, fed, last_tok, keys, chunked: bool):
    """Inside a decode step, first: every slot's token and key, each from
    where `fed` [B] says. `FED_HOST`: as the host uploaded them (`last_tok`,
    `keys`). `FED_STEP`: the token the step before drew for the slot,
    `prev[slot]`, which the host has not seen. `FED_CHUNK` (only where the
    step takes chunks, `chunked`): the slot's prompt ended in the step
    before, whose last three values are the request's first token and its
    decode stream key."""
    import jax
    import jax.numpy as jnp

    tok = jnp.where(fed == FED_STEP, prev[: last_tok.shape[0]], last_tok)
    if chunked:
        ended = fed == FED_CHUNK
        tok = jnp.where(ended, prev[-3], tok)
        stream = jax.lax.bitcast_convert_type(prev[-2:], jnp.uint32)
        keys = jnp.where(ended[:, None], stream[None], keys)
    return tok, keys


# ---------------------------------------------------------------------------
# the Llama family's step set (`LLAMA_STEPS` at the section's end): keys and
# values of every layer in the pool, nothing beside it
# ---------------------------------------------------------------------------


def _apply_rope_q(x, cos, sin):
    import jax.numpy as jnp

    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    # cos/sin [b, s, hd/2] → broadcast over heads
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def chunk_ladder(ecfg: EngineConfig) -> Tuple[int, ...]:
    """The static widths C > 0 of the prompt chunk a decode step may carry
    (`_make_decode_step`), ascending: a chunk of n prompt tokens runs at the
    narrowest width that holds it, a longer prompt in pieces of the widest.
    128 and 256 at max_model_len 2048, measured on a v5e at Mistral-7B
    widths, 16 layers, 32 slots (PERF.md section 6, PR 30): up to ~256 rows
    a chunk rides under the step's read of the weights at 12-17 us a row (a
    step of 13.9 ms takes 14.7 / 15.5 / 18.3 ms with 64 / 128 / 256 rows);
    past that the step is compute-bound at 44 us a row (29.3 ms with 512),
    every decoding slot waits that long for its token, and both closed-loop
    cells completed 5% fewer tokens a second with 512 as the widest. Each
    width is one more program to compile and to load before traffic (~1.7 s
    of every start from a warm compile cache): a third of 64 rows would
    save 0.8 ms on a quarter of chat's admissions, under 0.5% of a step.
    Every family whose prompts run as chunks takes these widths; PERF.md
    section 6 has the step's time by width on a v5e for Solar (PR 46) and
    for Brumby (PR 48)."""
    widest = min(256, max(8, 1 << ((ecfg.max_model_len // 4).bit_length() - 1)))
    return (widest // 2, widest)


def _step_params(cfg: LlamaConfig, params):
    """The decode step's tree: the given one with every layer's `wq`, `wk`
    and `wv` packed, columns [wq | wk | wv], into one leaf `wqkv` [layers,
    dim, (n_heads + 2 n_kv_heads) head_dim], so that the step's three input
    projections are one matmul whose weight streams from the stack (below).
    One concatenate on the device at the engine's start; every other leaf
    is the given tree's own buffer."""
    import jax.numpy as jnp

    qkv = ("wq", "wk", "wv")
    layers = {k: v for k, v in params["layers"].items() if k not in qkv}
    layers["wqkv"] = jnp.concatenate(
        [params["layers"][k] for k in qkv], axis=-1)
    return {**params, "layers": layers}


def _make_decode_step(cfg: LlamaConfig, ecfg: EngineConfig):
    """Build the jitted whole-batch single-token decode step, which may also
    carry one chunk of one admitting request's prompt; its `params` are
    `_step_params`'. Returns (step, path, note): which attention the decode
    rows were built with (`paged_attention.KERNEL` or `XLA`, decided here
    from the backend and the shapes) and, where a TPU was refused the
    kernel, why."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention

    bs = ecfg.kv_block_size
    max_blocks = -(-ecfg.max_model_len // bs)
    path, note = paged_attention.decode_path(
        cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs, cfg.dtype)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
    def paged_decode_step(C, params, kc, vc, tables, lens, active, last_tok,
                          keys, temps, prev, fed, chunk_ids=None,
                          chunk_at=None):
        """kc/vc [L, NB, BS, KV, HD]; tables [B, max_blocks] int32;
        lens/active/last_tok/fed [B]; keys [B,2] uint32; temps [B]; prev
        [B + 3], the step before's result (`feed_back`).
        Returns (next_tok [B] and three more, kc, vc).

        With a static chunk width C > 0 also chunk_ids [C], the next prompt
        tokens of the request in slot chunk_at[0] (an inactive row of the
        batch), at absolute positions chunk_at[1].. of which the first
        chunk_at[2] are real. The C rows go through every weight matmul
        with the B decode rows as one [B + C, D] batch (one read of the
        weights); their K and V are scattered into the slot's blocks
        (padding into the trash block) and they attend the slot's own
        table up to their positions: over a cached prefix, after earlier
        chunks or from position 0 alike. Behind the B tokens come the
        token drawn from the chunk's last real row with the slot's key
        (the request's first, where the chunk ends its prompt) and that
        key folded with 7, the slot's decode stream, as two int32; zeros
        without a chunk."""
        dt = cfg.dtype
        B = last_tok.shape[0]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=True)
        R = B + C
        hd = cfg.head_dim
        nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        ids = last_tok
        # one position a decode row, then the chunk's: two calls, so that a
        # decode step's positions stay one [B, 1] call
        cos, sin = (t.reshape(1, B, -1) for t in
                    rope_tables(cfg, lens[:, None]))
        # inactive slots write into the reserved trash block 0
        blk = jnp.clip(lens // bs, 0, max_blocks - 1)
        phys = jnp.where(
            active, tables[jnp.arange(B), blk], 0).astype(jnp.int32)
        off = (lens % bs).astype(jnp.int32)
        # the live context, read after the scatter: positions 0..lens
        # (the current token's included); an inactive slot attends nothing
        live = jnp.where(active, lens + 1, 0).astype(jnp.int32)
        if C:
            slot, start, n = chunk_at[0], chunk_at[1], chunk_at[2]
            row = tables[slot]
            qpos = start + jnp.arange(C, dtype=jnp.int32)
            ids = jnp.concatenate([ids, chunk_ids])
            ccos, csin = rope_tables(cfg, qpos[None])
            cos = jnp.concatenate([cos, ccos], axis=1)
            sin = jnp.concatenate([sin, csin], axis=1)
            phys = jnp.concatenate([phys, jnp.where(
                qpos < start + n,
                row[jnp.clip(qpos // bs, 0, max_blocks - 1)], 0)])
            off = jnp.concatenate([off, qpos % bs])
        h = params["tok_emb"].astype(dt)[ids][None]              # [1,R,D]

        def layer(carry, xs):
            # the pool rides in the carry and is written in place: as the
            # scan's xs/ys every layer's slice is copied out and back, and
            # the whole pool once more at the end
            h, kc, vc = carry
            p, l = xs
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            # one 2-D matmul over the packed leaf, cut into heads only after
            # it: the layer's weight then streams from the stack through the
            # dot, as wo's and the FFN's do. A dot whose result is reshaped
            # to heads at once has the reshape folded into it by the TPU's
            # compiler, which then wants the weight transposed and gets it
            # by staging the layer's slice in a buffer and copying that:
            # three serial passes a weight (PERF.md section 6, PR 35;
            # tests/test_v5e_compile.py holds the one-pass form)
            qkv = x @ p["wqkv"].astype(dt)                       # [1,R,·]
            q = qkv[..., :nq].reshape(1, R, cfg.n_heads, hd)
            k = qkv[..., nq:nq + nkv].reshape(1, R, cfg.n_kv_heads, hd)
            v = qkv[..., nq + nkv:].reshape(1, R, cfg.n_kv_heads, hd)
            q = _apply_rope_q(q, cos, sin).astype(dt)[0]
            k = _apply_rope_q(k, cos, sin).astype(dt)[0]
            kc = kc.at[l, phys, off].set(k)
            vc = vc.at[l, phys, off].set(v[0])
            o = paged_attention.decode_attention(
                path, q[:B], kc, vc, l, tables, live)            # [B,H,HD]
            if C:
                o = jnp.concatenate([o, paged_attention.chunk_attention(
                    q[B:], kc, vc, l, row, qpos, start + n)])
            h = h + o.reshape(1, R, -1) @ p["wo"].astype(dt)
            x2 = rms_norm(h, p["ln2"], cfg.norm_eps)
            gate = jax.nn.silu(x2 @ p["w1"].astype(dt))
            up = x2 @ p["w3"].astype(dt)
            h = h + (gate * up) @ p["w2"].astype(dt)
            return (h, kc, vc), None

        (h, kc, vc), _ = jax.lax.scan(
            layer, (h, kc, vc),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        h = h[0]
        if C:
            # the rows whose logits are read: B decode rows and the chunk's
            # last real one, sampled with the slot's own key and temperature
            h = jnp.concatenate(
                [h[:B], h[B + jnp.clip(n - 1, 0, C - 1)][None]])
            keys = jnp.concatenate([keys, keys[slot][None]])
            temps = jnp.concatenate([temps, temps[slot][None]])
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        logits = (h @ params["lm_head"].astype(dt)).astype(jnp.float32)
        toks = sample_tokens(keys, logits, temps)
        if C:
            stream = jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(keys[B]), 7))
            toks = jnp.concatenate(
                [toks, jax.lax.bitcast_convert_type(stream, jnp.int32)])
        else:
            toks = jnp.concatenate([toks, jnp.zeros((3,), jnp.int32)])
        return toks, kc, vc

    return paged_decode_step, path, note


def _make_prefill(cfg: LlamaConfig, ecfg: EngineConfig):
    """Jitted single-request prefill at a static padded length S: plain
    causal attention over the prompt, KV scattered into the request's
    blocks; returns (last_logits, kc, vc)."""
    import functools

    import jax
    import jax.numpy as jnp

    bs = ecfg.kv_block_size

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3))
    def paged_prefill(S, params, kc, vc, table, prompt, plen):
        """prompt [S] right-padded; table [max_blocks]; plen scalar."""
        dt = cfg.dtype
        hd = cfg.head_dim
        h = params["tok_emb"].astype(dt)[prompt][None]   # [1,S,D]
        pos = jnp.arange(S, dtype=jnp.int32)[None]
        cos, sin = rope_tables(cfg, pos)
        idx = jnp.arange(S)
        # scatter destinations; padded positions go to the trash block 0
        in_range = idx < plen
        phys = jnp.where(in_range, table[jnp.clip(idx // bs, 0,
                                                  table.shape[0] - 1)], 0)
        off = (idx % bs).astype(jnp.int32)
        causal = (idx[None, :, None] >= idx[None, None, :]) & (
            idx[None, None, :] < plen)  # [1,S,S] query x key validity

        def layer(carry, xs):
            h = carry
            p, kcl, vcl = xs
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            q = (x @ p["wq"].astype(dt)).reshape(1, S, cfg.n_heads, hd)
            k = (x @ p["wk"].astype(dt)).reshape(1, S, cfg.n_kv_heads, hd)
            v = (x @ p["wv"].astype(dt)).reshape(1, S, cfg.n_kv_heads, hd)
            q = _apply_rope_q(q, cos, sin).astype(dt)
            k = _apply_rope_q(k, cos, sin).astype(dt)
            kcl = kcl.at[phys, off].set(k[0])
            vcl = vcl.at[phys, off].set(v[0])
            kk, vv = k, v
            if cfg.n_kv_heads != cfg.n_heads:
                rep = cfg.n_heads // cfg.n_kv_heads
                kk = jnp.repeat(kk, rep, axis=2)
                vv = jnp.repeat(vv, rep, axis=2)
            scale = 1.0 / math.sqrt(hd)
            lg = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                            preferred_element_type=jnp.float32) * scale
            lg = jnp.where(causal[:, None], lg, -1e30)
            probs = jax.nn.softmax(lg, axis=-1).astype(dt)
            o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv)
            h = h + o.reshape(1, S, -1) @ p["wo"].astype(dt)
            x2 = rms_norm(h, p["ln2"], cfg.norm_eps)
            gate = jax.nn.silu(x2 @ p["w1"].astype(dt))
            up = x2 @ p["w3"].astype(dt)
            h = h + (gate * up) @ p["w2"].astype(dt)
            return h, (kcl, vcl)

        h, (kc, vc) = jax.lax.scan(layer, h, (params["layers"], kc, vc))
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        last = h[0, jnp.clip(plen - 1, 0, S - 1)]
        logits = (last @ params["lm_head"].astype(dt)).astype(jnp.float32)
        return logits, kc, vc

    return paged_prefill


def _alloc_cache(cfg: LlamaConfig, ecfg: EngineConfig):
    """kc, vc [layers, blocks + the trash block, block size, KV heads, head
    dim]: every layer's keys and values under the engine's block table."""
    import jax.numpy as jnp

    kc = jnp.zeros((cfg.n_layers, ecfg.num_kv_blocks + 1, ecfg.kv_block_size,
                    cfg.n_kv_heads, cfg.head_dim), cfg.dtype)
    return kc, jnp.zeros_like(kc)


def prefill_fresh_pool(cfg: LlamaConfig, ecfg: EngineConfig, prefill, params,
                       prompt_ids: List[int]):
    """Run the jitted `prefill` over one prompt into a pool sized to exactly
    that prompt (+ trash block 0). Returns (last_logits, kc, vc, nb) with
    the prompt's KV in blocks 1..nb."""
    import jax.numpy as jnp

    p = list(prompt_ids) or [0]
    plen = len(p)
    nb = -(-plen // ecfg.kv_block_size)
    S = max(8, 1 << (plen - 1).bit_length())
    kc, vc = _alloc_cache(cfg, replace(ecfg, num_kv_blocks=nb))
    table = np.zeros((max(nb, 1),), np.int32)
    table[:nb] = np.arange(1, nb + 1)
    prompt = np.zeros((S,), np.int32)
    prompt[:plen] = p
    logits, kc, vc = prefill(
        S, params, kc, vc, jnp.asarray(table), jnp.asarray(prompt),
        jnp.int32(plen))
    return logits, kc, vc, nb


def _check_prefill(cfg: LlamaConfig, ecfg: EngineConfig, prefill, params,
                   prompt_ids: List[int]):
    """The jitted `prefill` on a pool of its own against
    `models.llama.forward` on the same prompt: two writings of one model."""
    import functools

    import jax

    from ray_tpu.models.llama import forward

    got = prefill_fresh_pool(cfg, ecfg, prefill, params, prompt_ids)[0]
    ref = jax.jit(functools.partial(forward, cfg))(
        params, np.asarray([list(prompt_ids)], np.int32))[0, -1]
    return got, ref


def _make_kv_inject(cfg: LlamaConfig, ecfg: EngineConfig):
    """The decode side of prefill/decode disaggregation: blocks `phys` of
    the pool take the keys and values another worker's prefill computed
    (`prefill_fresh_pool`'s blocks 1..nb)."""
    import jax

    def paged_kv_inject(kc, vc, phys, k, v):
        return kc.at[:, phys].set(k), vc.at[:, phys].set(v)

    return jax.jit(paged_kv_inject, donate_argnums=(0, 1))


LLAMA_STEPS = types.SimpleNamespace(
    CACHE_NAMES=("kc", "vc"), alloc_cache=_alloc_cache,
    step_params=_step_params, make_decode_step=_make_decode_step,
    chunk_ladder=chunk_ladder,
    make_prefill=_make_prefill, check_prefill=_check_prefill,
    COUNTERS=(), PROBE=(), SLOT_STATE=None, NO_PREFIX_CACHE=None,
    SNAPSHOT_STATE=None, SNAPSHOT_POLICY=None,
    make_kv_inject=_make_kv_inject,
    extra_stats=lambda cfg, cache, attn_positions_live: {})


# ---------------------------------------------------------------------------
# the scheduler
# ---------------------------------------------------------------------------


@dataclass
class _Request:
    rid: int
    prompt: List[int]
    max_tokens: int
    temperature: float
    seed: int
    queue: asyncio.Queue = None  # type: ignore[assignment]
    slot: int = -1
    produced: int = 0
    # tokens of the steps dispatched for it, fetched or not: a stop the host
    # can count is applied when this reaches it, `produced` follows at the
    # fetches
    scheduled: int = 0
    admitted_mid_decode: bool = False
    # consumer walked away (client disconnect / stream cancel): the engine
    # loop drops it from the waiting queue or releases its slot + blocks
    # at the next step boundary instead of decoding for nobody
    aborted: bool = False
    # monotonic stamps of the request's phases (0.0 = not reached): they
    # feed stats()' ttft/queue-wait medians and, for a traced request, the
    # engine:queue / engine:prefill / engine:decode spans
    t_start: float = 0.0   # enqueued
    t_admit: float = 0.0   # admission began (slot and blocks in hand)
    t_first: float = 0.0   # first token emitted
    t_done: float = 0.0    # finished, failed or dropped
    # admission in chunks: the pool holds the prompt's positions [0, cursor)
    # (blocks hit in the prefix cache, then the chunks run so far); the
    # prompt's block keys, for the cache
    cursor: int = 0
    block_keys: tuple = ()
    # with state snapshots: where the matched blocks end (the chunks write
    # no keys or values before it) and the pool entry the first chunk starts
    # from (-1: none; pinned until that chunk is dispatched)
    cached_len: int = 0
    restore: int = -1
    # the snapshot policy's (`llm/_prefix_cache.SnapshotPolicy`): the
    # position, a block boundary, at which it has this prompt end a chunk and
    # leave a snapshot (0: nowhere), and its record of those the request
    # took and keeps
    take_at: int = 0
    kept: Any = None
    # the caller's span (the `completions_stream` execution span) when the
    # request is traced; the three spans are recorded as its children
    trace_parent: Optional[dict] = None
    # disaggregated serving: prefill ran on ANOTHER worker; admission
    # injects the transferred blocks instead of running the prompt
    # (reference: serving_patterns/prefill_decode — KV transfer between
    # prefill and decode engines): (*blocks, last_logits), one
    # [layers, nb, ...] a cache array (Llama: k, v [L, nb, bs, kvh, hd])
    prefilled: Optional[tuple] = None
    # check_routing's request: receives under "routing" the expert layers'
    # choices at every position computed ([moe_layers, n, top_k + 1] a piece)
    # and, where it has the key "steps", what each decode step computed the
    # slot's router and recurrence from, with the state before and after
    probe: Optional[Dict[str, Any]] = None


def _request_key(req: _Request) -> Tuple[int, int]:
    """The raw data of `jax.random.PRNGKey(seed * 1000003 + rid)`, the key
    `_sample_first` draws a request's first token with, without a dispatch
    to the device: a 32-bit seed's key is (0, seed), and a wider one is cut
    to its low 32 bits (no 64-bit types here; tests hold the two equal)."""
    return 0, (req.seed * 1000003 + req.rid) & 0xFFFFFFFF


CHUNK_PROBE = "chunk_"     # a probe's keys that are of the chunk's rows


def _mechanisms(probe: Dict[str, Any]) -> Dict[str, Any]:
    """Of a step's fetched probe, what it computed the probed slot's router
    and recurrence from."""
    return {k: v for k, v in probe.items()
            if k != "routing" and not k.startswith(CHUNK_PROBE)}


@dataclass
class _Step:
    """A dispatched decode step, kept until its tokens are fetched: what
    the loop needs to hand them out as the slots stood at the dispatch,
    whatever was released or admitted since."""
    toks: Any                       # the step's int32 vector, on the device
    probe: Any                      # its last result; None where none asked
    # the slots that decoded in it, each with the request it held then
    rows: List[Tuple[int, _Request]]
    # (request, real tokens, width) of the prompt chunk it carried, and
    # whether that chunk ended the prompt
    chunk: Optional[tuple]
    chunk_ends: bool
    probe_slot: Optional[int]       # whose mechanisms `probe` holds
    ahead: bool                     # dispatched with a step still unfetched
    live: int                       # positions its decode rows attended
    shared: int                     # of those, in blocks several requests hold

    @property
    def width(self) -> int:
        """The width of the chunk it carried, 0 for none."""
        return self.chunk[2] if self.chunk is not None else 0


class PagedEngine:
    """The continuous-batching scheduler around a family's jitted steps.

    Host-side state (block free list, slot table, request queues) is plain
    Python owned by ONE engine loop task; device state (block pool, tables)
    crosses in as arrays each step. Run it inside an async actor and call
    `generate_stream` concurrently — requests arriving mid-decode are
    admitted at the next step boundary.

    The loop runs one step ahead of its results (`_run_loop`): the slot
    arrays below describe the step to dispatch next, and the tokens of the
    one before are still on the device when it goes.

    `cfg` is the config of a family in `ray_tpu.llm.MODEL_FAMILIES`; all
    the engine knows of the family is its step set (the module docstring)."""

    def __init__(self, cfg, params, ecfg: Optional[EngineConfig] = None,
                 eos_id: Optional[int] = None):
        from ray_tpu.llm import step_set

        self.cfg = cfg
        self.ecfg = ecfg or EngineConfig()
        self._steps = steps = step_set(cfg)
        # the device arrays every step is given, donates and returns
        self._cache_names = steps.CACHE_NAMES
        # the given tree, under the family's names; beside it the decode
        # step's own (the same tree, or one that shares all it can)
        self.params = params
        self._step_params = steps.step_params(cfg, params)
        self.eos_id = eos_id
        e = self.ecfg
        self.bs = e.kv_block_size
        self.max_blocks = -(-e.max_model_len // self.bs)
        B = e.max_num_seqs
        self.tables = np.zeros((B, self.max_blocks), np.int32)
        self.lens = np.zeros((B,), np.int32)
        self.active = np.zeros((B,), bool)
        # the token of a slot the host activated itself; a slot that
        # decodes takes its token from the step before, on the device (`fed`)
        self.last_tok = np.zeros((B,), np.int32)
        self.fed = np.full((B,), FED_HOST, np.int32)
        self.temps = np.zeros((B,), np.float32)
        self.slot_req: List[Optional[_Request]] = [None] * B
        # the chunk widths the decode step takes. With a ladder a prompt is
        # admitted in chunks that ride in the decode steps (`_admit_chunks`);
        # with none whole, awaited in the loop (`_admit_whole`)
        self._ladder: Tuple[int, ...] = steps.chunk_ladder(e)
        refusal = steps.NO_PREFIX_CACHE
        # does a chunk say where it resumes (`chunk_at` of six)
        self._resumes = steps.SNAPSHOT_STATE is not None
        if self._resumes and e.num_state_snapshots < 2 and not refusal:
            refusal = (
                "prefix_cache=True with recurrent layers needs a pool of "
                "state snapshots: num_state_snapshots >= 2, not "
                f"{e.num_state_snapshots}")
        if refusal and e.prefix_cache:
            raise ValueError(refusal)
        # the prefix cache and, where a block resumes a sequence only from a
        # snapshot of its slot's state, the step set's policy beside it
        self._prefix_cache = self._snapshots = None
        if not refusal and e.prefix_cache is not False:
            from ray_tpu.llm._prefix_cache import PrefixCache

            self._prefix_cache = PrefixCache(
                self.bs, e.num_state_snapshots if self._resumes else 0)
            if self._resumes:
                self._snapshots = steps.SNAPSHOT_POLICY(
                    self._prefix_cache, self._ladder[-1])
        # requests admitted past `engine:prefix_match`, and the seconds spent
        # inside it (hashing, matching, eviction; tries that found no room
        # too): what an admission's bookkeeping costs, over a whole window
        self.admissions = 0
        self.admit_host_s = 0.0
        # matched tokens run again because the deepest snapshot lay before
        # the matched blocks' end; the positions a step's chunk attended (up
        # to its end) and its query-key pairs (row i of a chunk from `at`
        # sees at + i + 1 keys)
        self.snapshot_rerun_tokens = 0
        # requests that resumed from a snapshot another request's prompt left
        self.snapshots_shared = 0
        self.chunk_positions_live = 0
        self.chunk_attn_pairs = 0
        self._alloc_device_state()
        # "paged_kernel" | "xla", fixed for the engine's life (stats())
        self._decode, self.decode_attention, self._decode_note = (
            steps.make_decode_step(cfg, e))
        if self._decode_note:
            logging.getLogger(__name__).warning(self._decode_note)
        # whole prompts, where the step set has the program: without a
        # ladder the loop's; check_prefill
        make_prefill = getattr(steps, "make_prefill", None)
        if make_prefill is None and not self._ladder:
            raise ValueError(
                "a step set with an empty chunk_ladder runs its prompts "
                "whole and must bring make_prefill and check_prefill")
        self._prefill = (None if make_prefill is None
                         else make_prefill(cfg, e))
        # admitted requests whose prompts are not all in the pool yet, in
        # arrival order, and whose turn the next chunk step is: the oldest's,
        # else that of the one with the least left to run (`_next_chunk`)
        self._prefilling: "collections.deque[_Request]" = collections.deque()
        self._oldest_turn = True
        self._pending: "asyncio.Queue[_Request]" = None  # type: ignore
        self._inject = None  # the step set's KV scatter, at first use (P/D)
        self._loop_task = None
        self._rid = 0
        self._rngs = np.zeros((B, 2), np.uint32)
        self.steps = 0
        self.tokens_out = 0
        # steps dispatched while the one before was unfetched, and rows
        # computed for a sequence that had ended (a token or an abort the
        # loop saw a step late)
        self.steps_ahead = 0
        self.rows_dropped = 0
        self.mid_decode_admissions = 0
        # chunks run (one a step that carries any), the prompt tokens in
        # them and the rows of padding beside those
        self.prefill_chunks = 0
        self.prefill_chunk_tokens = 0
        self.prefill_chunk_pad_tokens = 0
        # chunk steps given to a request that was not the oldest in chunks
        self.chunk_overtakes = 0
        # positions decode attention had to read (each active slot's context,
        # the current token's included) and what scoring max_model_len
        # positions of every slot reads: their ratio is the live share
        self.attn_positions_live = 0
        self.attn_positions_dense = 0
        # of the live positions, those in blocks that more than one admitted
        # request holds (the prefix cache's counts): what reading a shared
        # prefix once a step, and not once a row, would save
        self.attn_positions_shared = 0
        # what the decode step counts on the device and returns behind its
        # tokens, summed over the steps
        self._step_counters = {name: 0 for name in steps.COUNTERS}
        # the slot whose router and recurrence inputs the decode step hands
        # out (check_routing; one request at a time), and its device copy
        self._probe_slot = None
        # turns of the loop (from one fetch of a step's tokens to the
        # next: emit, sweep, admissions, a dispatch, the wait) that took
        # over STALL_TURN_S: how many, their seconds, of those the seconds
        # before the dispatch, and when the last one ended (unix time)
        self._stalls = {"loop_stalls": 0, "loop_stall_s": 0.0,
                        "loop_stall_admit_s": 0.0, "loop_stall_last_at": 0.0}
        # the loop's account of its own time, on the same clock reads, with
        # a profiler or none (`_account_turn`): the seconds of the turns that
        # fetched a step (`loop_turn_s`), of those the seconds inside the
        # fetch (`loop_wait_s`: `engine:device_wait`'s edges), the turns
        # whose fetch found its tokens ready and their seconds, and the
        # seconds an empty engine waited (`loop_idle_s`: `engine:idle`'s
        # edges, added when the wait ends). A turn is filed under the chunk
        # width of the step it fetched (`steps_w<w>`, `turn_s_w<w>`): where
        # the device sets the pace that is what that kind of step takes,
        # where the host does, the host's turn. The sums over the widths are
        # `loop_turn_s` and `steps`; `loop_wait_s` <= `loop_turn_s`; and
        # `loop_turn_s` + `loop_idle_s` is all of the loop's time but the
        # sweeps that found the engine empty, before each idle wait.
        self._account = {"loop_turn_s": 0.0, "loop_wait_s": 0.0,
                         "loop_idle_s": 0.0, "turns_unwaited": 0,
                         "turn_unwaited_s": 0.0}
        for width in (0, *self._ladder):
            self._account[f"steps_w{width}"] = 0
            self._account[f"turn_s_w{width}"] = 0.0
        self._ttfts = collections.deque(maxlen=256)
        self._queue_waits = collections.deque(maxlen=256)

    # -- device-state recovery -----------------------------------------

    def _cache(self) -> tuple:
        return tuple(getattr(self, n) for n in self._cache_names)

    def _set_cache(self, arrays) -> None:
        for name, a in zip(self._cache_names, arrays):
            setattr(self, name, a)

    def _step_inputs(self, put, prev) -> list:
        """What every step takes after the caches, in the step's order: the
        slot arrays through `put`, and `prev` for the result of the step
        before."""
        slots = [put(a) for a in (self.tables, self.lens, self.active,
                                  self.last_tok, self._rngs, self.temps)]
        return [*slots, prev, put(self.fed)]

    def _device_state_invalid(self) -> bool:
        try:
            return any(a.is_deleted() for a in self._cache())
        except AttributeError:
            return False

    def _alloc_device_state(self):
        """Allocate the caches + free-block list (block 0 is the trash
        block). Shared by __init__ and post-failure reset so the pool
        layout can never diverge between the two."""
        import jax.numpy as jnp

        self._set_cache(self._steps.alloc_cache(self.cfg, self.ecfg))
        # the last dispatched step's result and, until its tokens are
        # fetched, its record
        self._toks = jnp.zeros((
            self.ecfg.max_num_seqs + len(self._steps.COUNTERS)
            + (3 if self._ladder else 0),), jnp.int32)
        self._flight: Optional[_Step] = None
        if self._steps.PROBE:
            self._probe_arg = jnp.int32(0)
        self.free_blocks = list(range(1, self.ecfg.num_kv_blocks + 1))

    def _reset_device_state(self):
        """Reallocate the caches and clear host bookkeeping. Needed when a
        jitted step fails AFTER its donated inputs were invalidated: every
        in-flight sequence lost its cache, so the engine must start from an
        empty pool rather than leave the attributes pointing at deleted
        buffers (every later request would die with a confusing
        'buffer donated/deleted' error; advisor r3)."""
        self._alloc_device_state()
        self.tables[:] = 0
        self.lens[:] = 0
        self.active[:] = False
        self.last_tok[:] = 0
        self.fed[:] = FED_HOST
        self.temps[:] = 0.0
        self.slot_req = [None] * self.ecfg.max_num_seqs
        self._prefilling.clear()
        if self._prefix_cache is not None:
            # cached blocks pointed into the old (destroyed) pool
            self._prefix_cache.clear()
        self._publish_metrics()

    # -- admission ------------------------------------------------------

    def _blocks_needed(self, req: _Request) -> int:
        total = min(len(req.prompt) + req.max_tokens, self.ecfg.max_model_len)
        return -(-total // self.bs)

    def _free_with_eviction(self, want: int) -> bool:
        """True if the free list holds ``want`` blocks, evicting zero-ref
        prefix-cache blocks (LRU) to get there — cached-but-unused blocks
        are capacity, never a reason to refuse admission."""
        short = want - len(self.free_blocks)
        if short > 0 and self._prefix_cache is not None:
            self.free_blocks.extend(self._prefix_cache.evict(short))
        return len(self.free_blocks) >= want

    def _try_admit(self, req: _Request) -> bool:
        import jax

        # state_reset: the admission hands what the slot carries beside its
        # blocks to a new request (whose prefill overwrites it)
        with jax.profiler.TraceAnnotation(
                PHASE_ADMIT,
                state_reset=int(self._steps.SLOT_STATE is not None)):
            return self._admit(req)

    def _admit(self, req: _Request) -> bool:
        """A slot, the blocks (those the prefix cache already holds, then
        new ones) and the slot's table row; then the prompt, by the one
        fork the step set's ladder decides. False: the head waits for a slot
        or for blocks."""
        t_admit = time.monotonic()
        need = self._blocks_needed(req)
        try:
            slot = next(i for i, r in enumerate(self.slot_req) if r is None)
        except StopIteration:
            return False
        if req.prefilled is not None:
            return self._admit_prefilled(req, slot, need, t_admit)
        import jax

        cache, snapshots = self._prefix_cache, self._snapshots
        plen = len(req.prompt)
        hits: List[int] = []
        keys: List[bytes] = []
        resume, restore, take_at = 0, -1, 0
        span = jax.profiler.TraceAnnotation(PHASE_PREFIX_MATCH)
        t_match = time.monotonic()
        with span:
            # a check's request may ask to run from position 0 whatever is
            # cached (`check_routing(cold=True)`)
            cold = req.probe is not None and req.probe.get("cold")
            if cache is not None:
                from ray_tpu.llm._prefix_cache import chain_keys

                keys = chain_keys(req.prompt, self.bs)
            matches = cache is not None and not cold
            # the policy may have the head wait for what the prompts still
            # in chunks are yet to run
            waits = (matches and snapshots is not None
                     and snapshots.waits(keys, self._prefilling))
            if matches and not waits:
                # reuse is capped one token short of the prompt: the LAST
                # prompt token must run through prefill locally or there
                # are no logits to sample the first generated token from
                hits = cache.match(keys[: (plen - 1) // self.bs])
            need_new = need - len(hits)
            fits = not waits and self._free_with_eviction(need_new)
            resume = len(hits) * self.bs
            if fits and hits and snapshots is not None:
                # the blocks resume the sequence only from a snapshot of
                # the slot's state, at or before their end
                resume, restore, take_at = snapshots.resume(keys, len(hits))
                if restore >= 0 and cache.snapshot_owner(restore) != req.rid:
                    self.snapshots_shared += 1
            span.set_metadata(cached_len=len(hits) * self.bs,
                              resume_from=resume)
        self.admit_host_s += time.monotonic() - t_match
        if not fits:
            if cache is not None:
                cache.cancel_match(hits)
            return False
        req.t_admit = t_admit
        self.admissions += 1
        blocks = [self.free_blocks.pop() for _ in range(need_new)]
        row = np.zeros((self.max_blocks,), np.int32)
        row[: need] = hits + blocks
        self.tables[slot] = row
        if self._ladder:
            self._admit_chunks(req, slot, hits, keys, resume, restore,
                               take_at)
        else:
            self._admit_whole(req, slot, row, blocks)
        return True

    def _admit_chunks(self, req: _Request, slot: int, hits: List[int],
                      keys: List[bytes], resume: int, restore: int,
                      take_at: int = 0):
        """With a chunk ladder an admission is bookkeeping only: the prompt
        past the cached blocks (with state snapshots: past the snapshot
        `restore` at position `resume`, at or before their end; `take_at`:
        where it is to leave one of its own, 0 for nowhere) runs as chunks
        of the coming steps (`_next_chunk`). The slot is the request's from
        here, so the abort sweep finds it."""
        req.slot, self.slot_req[slot] = slot, req
        req.cursor, req.block_keys = resume, tuple(keys)
        req.cached_len, req.restore = len(hits) * self.bs, restore
        req.take_at = take_at
        self.snapshot_rerun_tokens += req.cached_len - resume
        if restore >= 0:
            self._prefix_cache.pin_snapshot(restore)
        if req.probe is not None:
            req.probe["resume_from"] = resume
            if "steps" in req.probe:
                self._probe_admitted(req, slot)
                state0 = req.probe["state0"]
                if state0 is not None:
                    req.probe["state0"] = (
                        np.asarray(getattr(self, self._steps.SNAPSHOT_STATE)[
                            restore]) if restore >= 0
                        else np.zeros_like(state0))
        self._prefilling.append(req)
        if hits:
            from ray_tpu.util.metrics import Counter

            Counter("rt_llm_prefix_hits_total",
                    "KV blocks reused from the prompt-prefix cache "
                    "instead of re-prefilled.").inc(len(hits))

    def _admit_whole(self, req: _Request, slot: int, row, blocks: List[int]):
        """Without a chunk ladder the whole prompt runs here, awaited in the
        loop, through the step set's prefill at the prompt's power-of-two
        bucket S:

            paged_prefill(S, params, *cache, table [max_blocks], prompt [S]
                          right-padded, plen, slot)
                -> (last logits [V], routing, *cache)

        It writes the prompt's blocks and the whole of what slot `slot`
        carries beside them; `routing` ([layers, S, ...]) is read only for a
        probed request. The first token is sampled on the host."""
        import jax
        import jax.numpy as jnp

        plen = len(req.prompt)
        try:
            S = max(8, 1 << (plen - 1).bit_length())  # pow-2 bucket
            prompt = np.zeros((S,), np.int32)
            prompt[:plen] = req.prompt
            with jax.profiler.TraceAnnotation(
                    PHASE_PREFILL, S=S, cached_len=0):
                logits, routing, *caches = self._prefill(
                    S, self.params, *self._cache(), jnp.asarray(row),
                    jnp.asarray(prompt), jnp.int32(plen), jnp.int32(slot))
                self._set_cache(caches)
                if req.probe is not None:
                    req.probe["routing"].append(
                        np.asarray(routing)[:, :plen])
                    self._probe_admitted(req, slot)
            tok = self._sample_first(req, slot, logits)
        except BaseException:
            # any failure between the block pop and slot activation (prefill
            # trace/compile error, XLA OOM in sampling) must hand the blocks
            # back, or a few failing requests drain free_blocks and admission
            # deadlocks; the donated-invalid case is rebuilt by the caller
            # via _reset_device_state, which recreates free_blocks anyway
            self.free_blocks.extend(blocks)
            self.tables[slot] = 0
            raise
        self._activate_slot(req, slot, tok)

    def _next_chunk(self):
        """The admitted request whose prompt the next step runs a chunk of,
        and what of it: (request, tokens, width), the width the narrowest of
        the ladder that holds the tokens; None when no prompt is waiting.
        Chunk steps alternate between the oldest request in chunks and the
        one with the fewest prompt tokens left (ties to the older): a short
        prompt passes the long ones ahead of it, and the oldest still
        advances on every second chunk step at least, so no prompt starves
        and none takes more than twice the steps it takes alone. The rule
        reads only what the queue holds; with one request in chunks, or both
        choices the same, it is the oldest's chunk every step."""
        if any(r.slot < 0 for r in self._prefilling):
            # released by the abort sweep, wherever they stood
            self._prefilling = collections.deque(
                r for r in self._prefilling if r.slot >= 0)
        if not self._prefilling:
            return None
        req = self._prefilling[0]
        if not self._oldest_turn:
            req = min(self._prefilling, key=lambda r: len(r.prompt) - r.cursor)
        n = min(len(req.prompt) - req.cursor, self._ladder[-1])
        if self._snapshots is not None:
            n = self._snapshots.cut(req, n)
        return req, n, next(c for c in self._ladder if c >= n)

    def _chunk_at(self, req: Optional[_Request], at: int, n: int):
        """(the `chunk_at` a step is given for `n` tokens of `req`'s prompt
        from position `at`, the pool entry it takes a snapshot into or -1).
        `req` None: an idle chunk of no slot's (`warm_up`). Whether the
        chunk's end is a place for a snapshot is the policy's to say."""
        trash = self.ecfg.num_state_snapshots
        if req is None:
            return np.asarray(
                [0, 0, 0] + ([0, -1, trash] if self._resumes else []),
                np.int32), -1
        if not self._resumes:
            return np.asarray([req.slot, at, n], np.int32), -1
        take = (-1 if self._snapshots is None
                else self._snapshots.take(req, at + n))
        restore = req.restore
        if restore >= 0:
            # the step copies it in: from here on it may be displaced
            self._prefix_cache.pin_snapshot(restore, False)
            req.restore = -1
        return np.asarray(
            [req.slot, at, n, req.cached_len, restore,
             take if take >= 0 else trash], np.int32), take

    def _chunk_dispatched(self, req: _Request, n: int, take: int = -1):
        """A step that carries `n` tokens of `req`'s prompt is on its way:
        move the cursor, offer the blocks it completes to the prefix cache
        (every step dispatched from here on runs after the one that writes
        them; a request admitted while the prompt is still in chunks can
        hit only these) and, if the prompt is through, have the slot decode
        from the next step on, with the first token and the stream key this
        step leaves on the device (`FED_CHUNK`)."""
        slot = req.slot
        req.cursor += n
        # a dispatched chunk spends the turn, whoever it went to
        self.chunk_overtakes += req is not self._prefilling[0]
        self._oldest_turn = not self._oldest_turn
        if self._prefix_cache is not None:
            # every FULL prompt block in the pool (matched, then written by
            # the chunks so far) is cacheable; this request holds one ref on
            # each until release
            full = req.cursor // self.bs
            self._prefix_cache.register(
                req.block_keys[:full], self.tables[slot][:full])
            if take >= 0:
                # the step copies the slot's state into entry `take` after
                # the chunk
                key = req.block_keys[full - 1]
                if self._prefix_cache.attach_snapshot(key, take, req.rid):
                    self._snapshots.attached(req, key)
        if req.cursor < len(req.prompt):
            return
        self._prefilling.remove(req)
        if req.admitted_mid_decode:
            self.mid_decode_admissions += 1
        self.lens[slot] = len(req.prompt)
        self.active[slot] = True
        self.fed[slot] = FED_CHUNK
        self._publish_metrics()
        self._row_dispatched(req)

    def _row_dispatched(self, req: _Request):
        """One more token of `req` is on its way. A stop the host can count
        is applied here: the slot sits out the next step and its blocks are
        free for the admission of this turn, while the request's last
        tokens are still to be fetched."""
        req.scheduled += 1
        if (req.scheduled >= req.max_tokens or len(req.prompt)
                + req.scheduled >= self.ecfg.max_model_len):
            self._free_slot(req)

    def _emit(self, req: _Request, tok: int):
        req.produced += 1
        self.tokens_out += 1
        if req.produced == 1:
            req.t_first = time.monotonic()
            self._ttfts.append(req.t_first - req.t_start)
            self._queue_waits.append(req.t_admit - req.t_start)
        eos = self.eos_id is not None and tok == self.eos_id
        if not eos:
            req.queue.put_nowait(tok)
        if (eos or req.produced >= req.max_tokens or len(req.prompt)
                + req.produced >= self.ecfg.max_model_len):
            req.queue.put_nowait(None)
            self._release(req)

    def _probe_admitted(self, req: _Request, slot: int):
        """A request that asked for the decode step's mechanisms owns the
        step's probe from here to its release; what its slot carries beside
        the blocks, as the prefill left it, is the replay's starting
        point."""
        import jax.numpy as jnp

        if "steps" not in req.probe:
            return
        assert self._probe_slot is None, "one probed request at a time"
        self._probe_slot, self._probe_arg = slot, jnp.int32(slot)
        req.probe["state0"] = self._slot_state(slot)

    def _slot_state(self, slot: int):
        """What slot `slot` carries beside its blocks; None where the
        blocks are all a sequence has."""
        name = self._steps.SLOT_STATE
        return None if name is None else np.asarray(
            getattr(self, name)[:, slot])

    def _release(self, req: _Request):
        """The request is over (its last token emitted, aborted or failed):
        its slot and blocks return, unless a counted stop returned them at
        the dispatch of its last step. A row that a step in flight computes
        for it is dropped at the fetch."""
        ahead = self._flight
        if (ahead is not None and ahead.probe_slot is not None
                and any(s == ahead.probe_slot and r is req
                        for s, r in ahead.rows)
                and not self._device_state_invalid()):
            # a checked sequence ended by a token the loop saw a step late:
            # the row in flight moved the slot's state once more, and what
            # it computed from belongs to the record the state is held to
            import jax

            req.probe["steps"].append(_mechanisms(jax.device_get(ahead.probe)))
        if req.slot >= 0:
            self._free_slot(req)
        self._finish(req)

    def _free_slot(self, req: _Request):
        slot = req.slot
        if slot == self._probe_slot:
            self._probe_slot = None
            if not self._device_state_invalid():
                req.probe["state"] = self._slot_state(slot)
        need = self._blocks_needed(req)
        cache = self._prefix_cache
        if req.restore >= 0:
            # released before its first chunk went
            cache.pin_snapshot(req.restore, False)
            req.restore = -1
        for b in self.tables[slot][:need]:
            b = int(b)
            if b == 0:
                continue
            if cache is not None and cache.decref_block(b):
                continue  # cache-owned: stays resident, evictable at 0 refs
            self.free_blocks.append(b)
        self.tables[slot] = 0
        self.active[slot] = False
        self.fed[slot] = FED_HOST
        self.slot_req[slot] = None
        req.slot = -1
        self._publish_metrics()

    def _finish(self, req: _Request):
        """The request left the engine (done, aborted or failed): stamp it
        and, if its caller was traced, record the phases it reached as
        child spans of the caller's span."""
        if req.t_done:
            return
        req.t_done = time.monotonic()
        if req.trace_parent is None:
            return
        wall = time.time() - time.monotonic()
        # each phase ends where the next one reached begins
        end = req.t_done
        for name, start in ((SPAN_DECODE, req.t_first),
                            (SPAN_PREFILL, req.t_admit),
                            (SPAN_QUEUE, req.t_start)):
            if start:
                tracing.record_interval(req.trace_parent, name,
                                        start + wall, end + wall)
                end = start

    def _fail(self, req: _Request, error: Exception):
        req.queue.put_nowait(error)
        self._finish(req)

    def _sample_first(self, req: _Request, slot: int, logits):
        """Sample the first generated token + seed the slot's decode RNG —
        shared by local and prefilled admission (the seed formula and the
        fold_in MUST match or the two paths diverge)."""
        import jax

        with jax.profiler.TraceAnnotation(PHASE_SAMPLE_FIRST):
            key = jax.random.PRNGKey(req.seed * 1000003 + req.rid)
            if req.temperature > 0:
                tok = int(jax.random.categorical(
                    key, logits / max(req.temperature, 1e-6)))
            else:
                tok = int(np.argmax(np.asarray(logits)))
            self._rngs[slot] = np.asarray(
                jax.random.key_data(jax.random.fold_in(key, 7)), np.uint32)
        return tok

    def _activate_slot(self, req: _Request, slot: int, tok: int):
        """Final bookkeeping of the admissions that hand the host the first
        token (a whole prompt, transferred blocks): the slot decodes from
        the next step on, from the host's token."""
        self.slot_req[slot] = req
        if req.admitted_mid_decode:
            self.mid_decode_admissions += 1
        req.slot = slot
        req.scheduled = 1
        self.lens[slot] = len(req.prompt)
        self.active[slot] = True
        self.last_tok[slot] = tok
        self.fed[slot] = FED_HOST
        self.temps[slot] = req.temperature
        self._publish_metrics()
        self._emit(req, tok)

    def _admit_prefilled(self, req: _Request, slot: int, need: int,
                         t_admit: float) -> bool:
        """Admit a request whose prefill ran on ANOTHER worker: scatter the
        transferred block contents into this engine's pool (the step set's
        `make_kv_inject`) and seed the first token from the transferred
        last-position logits — the decode side of prefill/decode
        disaggregation (reference:
        serving_patterns/prefill_decode/builder.py:236-238 + the vLLM KV
        transfer connectors)."""
        import jax.numpy as jnp

        if self._inject is None:
            try:
                self._inject = self._steps.make_kv_inject(self.cfg, self.ecfg)
            except ValueError as refusal:
                # this family takes no transferred blocks: the request
                # fails, the queue moves on
                self._fail(req, refusal)
                return True
        if not self._free_with_eviction(need):
            return False
        req.t_admit = t_admit
        *blocks_in, last_logits = req.prefilled
        nb = blocks_in[0].shape[1]
        expect = -(-len(req.prompt) // self.bs)
        if nb != expect or nb > need:
            # malformed transfer: failing the REQUEST (not returning False,
            # which _run_loop reads as "wait for resources") keeps the
            # admission queue moving
            self._fail(req, ValueError(
                f"transferred KV has {nb} blocks; prompt of "
                f"{len(req.prompt)} tokens needs {expect} "
                f"(budget {need})"))
            return True
        blocks = [self.free_blocks.pop() for _ in range(need)]
        try:
            row = np.zeros((self.max_blocks,), np.int32)
            row[: len(blocks)] = blocks
            self.tables[slot] = row
            phys = jnp.asarray(np.asarray(blocks[:nb], np.int32))
            cache = self._cache()
            self._set_cache(self._inject(
                *cache, phys, *(jnp.asarray(b, c.dtype)
                                for b, c in zip(blocks_in, cache))))
            tok = self._sample_first(req, slot, jnp.asarray(last_logits))
        except BaseException:
            self.free_blocks.extend(blocks)
            self.tables[slot] = 0
            raise
        self._activate_slot(req, slot, tok)
        return True

    # -- engine loop ----------------------------------------------------

    async def _ensure_loop(self):
        if self._pending is None:
            self._pending = asyncio.Queue()
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.get_running_loop().create_task(
                self._run_loop())

    async def _run_loop(self):
        """One step ahead of its results. A turn dispatches step n+1 and
        only then fetches step n's tokens (the wait ends when step n ends
        on the device, with step n+1 queued behind it), emits them, sweeps
        and admits: all of the host's turn runs while the device computes.
        Step n+1 takes what step n sampled from the device (`feed_back`);
        what the host can count without the tokens (lengths, the keys'
        fold, a stop by `max_tokens` or `max_model_len`) it applies at the
        dispatch; a stop by a token or an abort it sees a step late, and
        drops the row that step computed (`rows_dropped`)."""
        import jax

        phase = jax.profiler.TraceAnnotation
        waiting: "collections.deque[_Request]" = collections.deque()
        t_turn = None      # the last fetch's end; None after an idle wait
        while True:
            if t_turn is None:
                t_turn = time.monotonic()
            mid_decode = bool(self.active.any())
            with phase(PHASE_SWEEP):
                while not self._pending.empty():
                    waiting.append(self._pending.get_nowait())
                # disconnect sweep: a consumer that walked away (client
                # abort, SSE timeout) releases its slot + KV blocks at this
                # step boundary — BEFORE admission, so the freed blocks
                # admit the waiting head this same tick instead of leaking
                # until OOM
                for r in list(self.slot_req):
                    if r is not None and r.aborted and r.slot >= 0:
                        self._release(r)
            # admit in arrival order while slots + blocks allow — requests
            # landing here while slots decode are the "admitted mid-decode"
            # continuous-batching case. With a chunk ladder an admission is
            # bookkeeping and the prompt rides in the steps below; without
            # one the prompt's whole prefill is awaited here, dispatched
            # behind the step in flight
            while waiting:
                req = waiting[0]
                if req.aborted:
                    waiting.popleft()  # consumer gone before admission
                    self._finish(req)
                    continue
                if self._blocks_needed(req) > self.ecfg.num_kv_blocks:
                    # can never fit even a drained pool: surface an ERROR,
                    # not a silently empty completion
                    waiting.popleft()
                    self._fail(req, ValueError(
                        f"request needs {self._blocks_needed(req)} KV "
                        f"blocks but the pool has "
                        f"{self.ecfg.num_kv_blocks}"))
                    continue
                req.admitted_mid_decode = mid_decode
                try:
                    ok = await asyncio.to_thread(self._try_admit, req)
                except Exception as e:  # noqa: BLE001 — admission failed
                    waiting.popleft()
                    self._fail(req, e)
                    if self._device_state_invalid():
                        # prefill donates the caches: a failure after donation
                        # destroyed every in-flight sequence's cache
                        self._fail_in_flight(e)
                    continue
                if not ok:
                    break  # head waits for blocks/slots to free
                waiting.popleft()
            chunk = self._next_chunk() if self._ladder else None
            flight = self._flight
            dispatch = chunk is not None or bool(self.active.any())
            if not dispatch and flight is None:
                # idle, nothing in flight: block until a request arrives
                # (one that landed during the turn is the next sweep's). The
                # one annotation held over an `await`: it opens and closes
                # here, around nothing but the wait, and every other event
                # on this thread (sweep, emit, a handler's) opens and closes
                # inside one slice of one coroutine, so whatever runs while
                # this waits lies whole inside it and none straddles its edge
                if self._pending.empty():
                    t_idle = time.monotonic()
                    with phase(PHASE_IDLE):
                        waiting.append(await self._pending.get())
                    self._account["loop_idle_s"] += time.monotonic() - t_idle
                t_turn = None
                continue
            t_step = time.monotonic()
            try:
                # one hop to a thread: the next step out (a token for every
                # active slot and, riding along, the next chunk of an
                # admitted prompt), then the wait for the one before
                fetched = await asyncio.to_thread(
                    self._run_step, dispatch, chunk, flight)
            except Exception as e:  # noqa: BLE001 — decode step failed
                # the device state is suspect, and so are both dispatched
                # steps: fail every in-flight and queued request (callers
                # must never hang on a dead loop)
                self._fail_in_flight(e, flight)
                while waiting:
                    self._fail(waiting.popleft(), e)
                while not self._pending.empty():
                    self._fail(self._pending.get_nowait(), e)
                raise
            if flight is not None:
                toks, probe, wait_s = fetched
                with phase(PHASE_EMIT):
                    self._emit_step(flight, toks, probe)
                now = time.monotonic()
                self._account_turn(flight.width, now - t_turn,
                                   t_step - t_turn, wait_s)
                t_turn = now
            await asyncio.sleep(0)  # let admissions interleave

    def _account_turn(self, width: int, turn_s: float, admit_s: float,
                      wait_s: float) -> None:
        """File a turn that fetched a step of chunk width `width`: its wall
        `turn_s`, of that the seconds before the dispatch and the seconds
        inside the fetch."""
        acc = self._account
        acc["loop_turn_s"] += turn_s
        acc["loop_wait_s"] += wait_s
        acc[f"steps_w{width}"] += 1
        acc[f"turn_s_w{width}"] += turn_s
        if wait_s < UNWAITED_S:
            acc["turns_unwaited"] += 1
            acc["turn_unwaited_s"] += turn_s
        if turn_s > STALL_TURN_S:
            self._stalls["loop_stalls"] += 1
            self._stalls["loop_stall_s"] += turn_s
            self._stalls["loop_stall_admit_s"] += admit_s
            self._stalls["loop_stall_last_at"] = time.time()

    def _run_step(self, dispatch: bool, chunk, flight: Optional[_Step]):
        """On the loop's thread hop: dispatch the next step (`dispatch`;
        `chunk` is `_next_chunk`'s) and apply what the host knows without
        its tokens, then fetch the tokens of `flight`, the step dispatched
        a turn earlier: (tokens, probe or None, the seconds the fetch
        waited), or None without one."""
        import jax
        import jax.numpy as jnp

        phase = jax.profiler.TraceAnnotation
        self._flight = None
        if not dispatch:
            return self._fetch(flight)
        width, chunk_args, ends = 0, (), False
        if chunk is not None:
            admitting, n, width = chunk
            at = admitting.cursor
            ends = at + n == len(admitting.prompt)
            ids = np.zeros((width,), np.int32)
            ids[:n] = admitting.prompt[at:at + n]
            chunk_at, take = self._chunk_at(admitting, at, n)
            chunk_args = (ids, chunk_at)
            # the slot's row of keys and temperatures is idle until it
            # decodes: the step draws the request's first token with them
            self._rngs[admitting.slot] = _request_key(admitting)
            self.temps[admitting.slot] = admitting.temperature
        # the step's static chunk width, where the step takes one
        lead = (width,) if self._ladder else ()
        rows = [(int(slot), self.slot_req[slot])
                for slot in np.flatnonzero(self.active)]
        probing = (any(req.probe is not None for _, req in rows)
                   or (chunk is not None and admitting.probe is not None))
        # the outer annotation names a device gap that straddles two of the
        # inner ones (else a Python frame and its line)
        with phase(PHASE_STEP, chunk=width):
            with phase(PHASE_UPLOAD):
                # copies: the slot arrays move on below, while the transfer
                # (on the CPU the step itself) may still read what it is given
                host = self._step_inputs(
                    lambda a: jnp.asarray(a.copy()), self._toks)
                host.extend(jnp.asarray(a) for a in chunk_args)
            if self._steps.PROBE:
                host.append(self._probe_arg)
            with phase(PHASE_DISPATCH):
                toks, *rest = self._decode(
                    *lead, self._step_params, *self._cache(), *host)
                n_cache = len(self._cache_names)
                self._set_cache(rest[:n_cache])
            # past the caches: what a check reads, kept only while a request
            # asks
            self._toks = toks
            probe = rest[n_cache] if probing else None
            if probing and self._probe_slot is None:
                # nobody's mechanisms are recorded: the routing alone
                probe = {k: v for k, v in probe.items()
                         if k.endswith("routing")}
            shared = (0 if self._prefix_cache is None else self.bs
                      * self._prefix_cache.shared_blocks(
                          self.tables[self.active]))
            self._flight = _Step(
                toks, probe, rows, chunk, ends,
                self._probe_slot, flight is not None,
                int(self.lens[self.active].sum() + self.active.sum()), shared)
            # what the host knows at dispatch it applies at dispatch
            self._rngs[:, 1] += 1  # fresh fold per step
            self.lens[self.active] += 1
            self.fed[self.active] = FED_STEP
            for _, req in rows:
                self._row_dispatched(req)
            if chunk is not None:
                self.chunk_positions_live += at + n
                self.chunk_attn_pairs += n * at + n * (n + 1) // 2
                self._chunk_dispatched(admitting, n, take)
            return self._fetch(flight)

    def _fetch(self, step: Optional[_Step]):
        import jax

        if step is None:
            return None
        t_wait = time.monotonic()
        with jax.profiler.TraceAnnotation(PHASE_DEVICE_WAIT,
                                          chunk=step.width):
            toks, probe = np.asarray(step.toks), (
                None if step.probe is None else jax.device_get(step.probe))
        return toks, probe, time.monotonic() - t_wait

    def _emit_step(self, step: _Step, toks, probe):
        """Hand out a fetched step's tokens as the slots stood when it was
        dispatched: a row whose request has ended since (by the token of
        the step before, or an abort) is dropped, whoever holds the slot
        now."""
        self.steps += 1
        self.steps_ahead += step.ahead
        self.attn_positions_live += step.live
        self.attn_positions_shared += step.shared
        self.attn_positions_dense += (
            self.ecfg.max_num_seqs * self.ecfg.max_model_len)
        # behind the tokens: the step's counters, then the chunk's
        tail = toks[len(self.slot_req):]
        for name, n in zip(self._step_counters, tail):
            self._step_counters[name] += int(n)
        for slot, req in step.rows:
            if req.t_done:
                self.rows_dropped += 1
                continue
            if req.probe is not None:
                if "routing" in probe:
                    req.probe["routing"].append(
                        probe["routing"][:, slot:slot + 1])
                if slot == step.probe_slot:
                    req.probe["steps"].append(_mechanisms(probe))
            self._emit(req, int(toks[slot]))
        if step.chunk is None:
            return
        req, n, width = step.chunk
        self.prefill_chunks += 1
        self.prefill_chunk_tokens += n
        self.prefill_chunk_pad_tokens += width - n
        if req.probe is not None and probe is not None and not req.t_done:
            # a checked request's prompt: its rows' routing and, for the one
            # whose mechanisms are recorded, what the recurrence ran on
            if CHUNK_PROBE + "routing" in probe:
                req.probe["routing"].append(
                    probe[CHUNK_PROBE + "routing"][:, :n])
            if "steps" in req.probe:
                req.probe["chunks"].append({
                    k[len(CHUNK_PROBE):]: v[:, :n] for k, v in probe.items()
                    if k.startswith(CHUNK_PROBE) and k != CHUNK_PROBE + "routing"})
        if step.chunk_ends and not req.t_done:
            first, *stream = tail[len(self._step_counters):]
            if req.slot >= 0:
                # the slot decodes on: the key of its next row is the
                # stream's, counted up once a row dispatched since
                self._rngs[req.slot] = np.asarray(
                    stream, np.int32).view(np.uint32)
                self._rngs[req.slot, 1] += np.uint32(req.scheduled - 1)
            self._emit(req, int(first))

    def _fail_in_flight(self, error: Exception, fetching=None):
        """A failed step or admission took the device state with it: every
        request in a slot or in a dispatched step gets the error, and the
        donated pool is rebuilt so that the next request starts from a
        clean engine."""
        steps = [s for s in (fetching, self._flight) if s is not None]
        self._flight = None
        held = [r for s in steps for _, r in s.rows]
        held += [s.chunk[0] for s in steps if s.chunk is not None]
        for req in held + list(self.slot_req):
            if req is not None and not req.t_done:
                req.queue.put_nowait(error)
                self._release(req)
        if self._device_state_invalid():
            self._reset_device_state()

    # -- public API -----------------------------------------------------

    def warm_up(self) -> None:
        """Compile and run once every program the loop can dispatch: the
        decode step at each chunk width and without one, on an idle batch
        whose rows all land in the trash block. After it no request, of
        whatever length, compiles anything. Before the loop starts (the
        caches are donated to each call). Without a chunk ladder the loop
        has a program a prompt-length bucket, and none is warmed."""
        import jax.numpy as jnp

        if not self._ladder:
            return
        host = self._step_inputs(jnp.asarray, self._toks)
        probe = (self._probe_arg,) if self._steps.PROBE else ()
        for width in (0, *self._ladder):
            chunk = (jnp.zeros((width,), jnp.int32),
                     jnp.asarray(self._chunk_at(None, 0, 0)[0])) if width else ()
            toks, *caches = self._decode(
                width, self._step_params, *self._cache(), *host, *chunk,
                *probe)
            self._set_cache(caches[:len(self._cache_names)])
            toks.block_until_ready()

    async def generate_stream(self, prompt_ids: List[int], *,
                              max_tokens: int = 32,
                              temperature: float = 0.0, seed: int = 0,
                              prefilled: Optional[tuple] = None,
                              probe: Optional[Dict[str, Any]] = None):
        """Async generator of token ids. Engine-side failures raise into the
        consumer (queue items: int token | None end | Exception).
        `prefilled=(*blocks, last_logits)` (Llama: k, v) admits with KV
        transferred from a remote prefill worker instead of running the
        prompt here. `probe`
        (see `check_routing`) receives what a check holds to a reference."""
        prompt_ids = list(prompt_ids) or [0]
        if len(prompt_ids) + 1 > self.ecfg.max_model_len:
            raise ValueError(
                f"prompt of {len(prompt_ids)} tokens exceeds "
                f"max_model_len={self.ecfg.max_model_len}")
        await self._ensure_loop()
        self._rid += 1
        req = _Request(self._rid, prompt_ids, int(max_tokens),
                       float(temperature), int(seed),
                       queue=asyncio.Queue(), prefilled=prefilled,
                       probe=probe, t_start=time.monotonic(),
                       trace_parent=tracing.current_span())
        self._pending.put_nowait(req)
        try:
            while True:
                tok = await req.queue.get()
                if tok is None:
                    return
                if isinstance(tok, Exception):
                    raise tok
                yield tok
        finally:
            # consumer gone — clean finish, exception, OR an abandoned
            # generator (client disconnect cancels the SSE stream and the
            # async generator is aclose()d). The engine loop releases the
            # slot + blocks at its next step boundary; without this flag a
            # cancelled stream leaked its KV blocks until pool exhaustion.
            req.aborted = True

    def check_prefill(self, prompt_ids: List[int]) -> Dict[str, Any]:
        """Prefill's last-position logits against the family's reference
        forward pass on the same prompt: the paged prefill step and the
        forward pass are two writings of one model and must agree. Runs on
        caches of its own, so the live ones are untouched. A family whose
        prompts run as chunks has no such program (`_no_prefill`)."""
        if self._prefill is None:
            raise ValueError(self._no_prefill())
        got, ref = map(np.asarray, self._steps.check_prefill(
            self.cfg, self.ecfg, self._prefill, self.params, prompt_ids))
        return {
            "prompt_tokens": len(prompt_ids),
            "finite": bool(np.isfinite(got).all()),
            "max_abs_diff": float(np.abs(got - ref).max()),
            "max_abs_ref": float(np.abs(ref).max()),
            "argmax_equal": bool(got.argmax() == ref.argmax()),
        }

    def _no_prefill(self) -> str:
        return (
            f"{type(self.cfg).__name__}'s prompts run as chunks in the "
            "decode step, so its step set brings no whole-prompt prefill "
            "program to check or to lower: check_routing runs a prompt "
            "through the served chunks and is this family's check")

    async def check_routing(self, prompt_ids: List[int], max_tokens: int,
                            mechanisms: bool = False, cold: bool = False
                            ) -> Dict[str, Any]:
        """One greedy request through the engine's loop, the timed path's
        own programs, with every expert layer's routing recorded: the tokens
        and, for each position computed (the prompt's, then one a decode
        step: prompt + max_tokens - 1 in all), the chosen experts and the
        kept-groups mask, "routing" [moe_layers, positions, top_k + 1].

        With `mechanisms` (one such request at a time) also what the router
        and the recurrence computed from at every decode step, stacked over
        the max_tokens - 1 steps under the keys of the step set's `PROBE`,
        and what the slot carries beside its blocks (`SLOT_STATE`) as the
        prefill left it ("state0") and after the last step ("state"),
        [layers, ...]: a reference given the same inputs must arrive at the
        same scores and state. Where the prompt ran as chunks, "state0" is
        the state its first chunk started from (a snapshot's, or zeros) and
        "chunks" what each chunk's recurrence ran on, a dict a chunk of
        [layers, tokens, ...].

        `cold` runs the prompt from position 0 whatever the prefix cache
        holds; "resume_from" says where it did start.

        A debug path beside `check_prefill`: a decode step computes these
        anyway and the loop fetches them only while such a request is in a
        slot."""
        probe: Dict[str, Any] = {"routing": [], "cold": cold}
        if mechanisms:
            probe["steps"], probe["chunks"] = [], []
        toks = [t async for t in self.generate_stream(
            prompt_ids, max_tokens=max_tokens, probe=probe)]
        out = {"token_ids": toks, "resume_from": probe.get("resume_from", 0)}
        if probe["routing"]:
            out["routing"] = np.concatenate(probe["routing"], axis=1)
        if mechanisms:
            steps = probe.pop("steps")
            out.update({k: np.stack([st[k] for st in steps])
                        for k in (steps[0] if steps else ())})
            out["state0"], out["state"] = probe["state0"], probe["state"]
            out["chunks"] = probe["chunks"]
        return out

    def step_hlo(self, prefill_lengths: List[int]) -> Dict[str, List[str]]:
        """The optimized HLO text of the compiled steps, by the program names
        the device trace shows: the decode step and the prefill at each
        prompt length's bucket (refused where the step set has no prefill:
        give it no lengths). Every instruction carries its `op_name`, the
        `jax.named_scope`s it was traced under included, which the trace's
        events do not; a reader joins the two by instruction name. Compiles
        from shapes alone (a hit in the compile cache for a step that has
        run), so it may run beside the loop."""
        import jax
        import jax.numpy as jnp

        if prefill_lengths and self._prefill is None:
            raise ValueError(self._no_prefill())

        def shape(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        params = jax.tree.map(shape, self.params)
        step_params = jax.tree.map(shape, self._step_params)
        cache = [shape(a) for a in self._cache()]
        host = self._step_inputs(shape, shape(self._toks))
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        probe = [i32] if self._steps.PROBE else []     # the probed slot

        def decode(*lead):
            chunk = [jax.ShapeDtypeStruct((lead[0],), jnp.int32),
                     shape(self._chunk_at(None, 0, 0)[0])] if any(lead) else []
            return self._decode.lower(
                *lead, step_params, *cache, *host, *chunk, *probe
            ).compile().as_text()

        # one program a chunk width, all under the one name
        widths = [(c,) for c in (0, *self._ladder)] if self._ladder else [()]
        out = {"jit_paged_decode_step": [decode(*w) for w in widths]}
        out["jit_paged_prefill"] = []
        for n in prefill_lengths:
            S = max(8, 1 << (n - 1).bit_length())
            args = [shape(self.tables[0]),
                    jax.ShapeDtypeStruct((S,), jnp.int32), i32]
            if not self._ladder:
                args.append(i32)       # `_admit_whole`: the slot it writes
            out["jit_paged_prefill"].append(self._prefill.lower(
                S, params, *cache, *args).compile().as_text())
        return out

    def _publish_metrics(self):
        """Engine telemetry on the metrics plane (constructors are
        idempotent — re-construction returns the registered instrument)."""
        from ray_tpu.util.metrics import Gauge

        e = self.ecfg
        evictable = (self._prefix_cache.evictable_blocks()
                     if self._prefix_cache is not None else 0)
        in_use = e.num_kv_blocks - len(self.free_blocks) - evictable
        Gauge("rt_llm_kv_blocks_in_use",
              "KV pool blocks held by in-flight sequences (zero-ref "
              "prefix-cache blocks count as free capacity).").set(in_use)
        Gauge("rt_llm_batch_occupancy",
              "Fraction of decode batch slots active.").set(
            float(self.active.sum()) / max(1, e.max_num_seqs))

    def stats(self) -> Dict[str, Any]:
        cache = self._prefix_cache
        evictable = cache.evictable_blocks() if cache is not None else 0
        ttfts = sorted(self._ttfts)
        queue_waits = sorted(self._queue_waits)
        out = {
            "steps": self.steps,
            "tokens_out": self.tokens_out,
            # free = immediately allocatable + reclaimable-by-eviction:
            # zero-ref cached blocks are capacity, and callers sizing
            # admission against free_blocks must see them as such
            "free_blocks": len(self.free_blocks) + evictable,
            "blocks_in_use": (self.ecfg.num_kv_blocks
                              - len(self.free_blocks) - evictable),
            "active_slots": int(self.active.sum()),
            "mid_decode_admissions": self.mid_decode_admissions,
            # of `steps`, those dispatched before the step ahead of them was
            # fetched (all but an idle engine's first), and the rows they
            # computed for sequences that had ended a step earlier
            "steps_ahead": self.steps_ahead,
            "rows_dropped": self.rows_dropped,
            "prefill_chunks": self.prefill_chunks,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefill_chunk_pad_tokens": self.prefill_chunk_pad_tokens,
            # one chunk a step: the same count until a step carries several
            "steps_with_chunk": self.prefill_chunks,
            # of the chunks dispatched, those not the oldest prompt's
            "chunk_overtakes": self.chunk_overtakes,
            "admissions": self.admissions,
            "admit_host_s": self.admit_host_s,
            "prefix_cache": cache.stats() if cache is not None else None,
            "attn_positions_live": self.attn_positions_live,
            "attn_positions_dense": self.attn_positions_dense,
            "attn_positions_shared": self.attn_positions_shared,
            "decode_attention": self.decode_attention,
        }
        if self._decode_note:
            out["decode_attention_note"] = self._decode_note
        out.update(self._step_counters)
        if self._resumes:
            out.update({
                name: getattr(cache, name, 0) for name in (
                    "snapshots_taken", "snapshots_restored",
                    "snapshots_evicted")})
            out["snapshots_shared"] = self.snapshots_shared
            out["snapshot_rerun_tokens"] = self.snapshot_rerun_tokens
            out["chunk_positions_live"] = self.chunk_positions_live
            out["chunk_attn_pairs"] = self.chunk_attn_pairs
        out.update(self._stalls)
        out.update(self._account)
        out.update(self._steps.extra_stats(
            self.cfg, self._cache(), self.attn_positions_live))
        if ttfts:
            # time to first token is queue wait + prefill: an operator
            # needs the split to tell a backlog from a slow prefill
            out["ttft_p50_s"] = ttfts[len(ttfts) // 2]
            out["queue_wait_p50_s"] = queue_waits[len(queue_waits) // 2]
        return out

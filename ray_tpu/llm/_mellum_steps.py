"""The Mellum 2 family's step set (`models/mellum.py`): the jitted step,
caches, counters and capabilities `PagedEngine` serves it by, under the names
of `llm/_engine.STEP_SET` (that module's docstring is the interface). A
sequence's memory is of two kinds, by the kind of layer:

    kc, vc  [full_layers, NB + 1, BS, KV, HD]  keys and values of the full
            layers, paged under the engine's block table: they grow with the
            sequence, 2 x KV x HD values a layer and position.
    wk, wv  [window_layers, slots, R, KV, HD]  keys and values of the window
            layers, a ring a slot: position p lies at ring index p mod R, and
            what lay there, position p - R, is behind every window that is
            still to be read. R = `_ring_positions`: the window and the widest
            chunk (a chunk writes its keys before its rows attend), in whole
            blocks. A slot's ring is the same bytes at position 2,000 and at
            32,000, and the scheduler knows nothing of it: no block of it is
            ever handed out or back. Keys are stored rotated, so their order
            in memory matters to no softmax, only which of them are live.

Seen as `[window_layers, slots * R / BS, BS, KV, HD]` (the same bytes) the
rings are a pool in `ops/paged_attention`'s own layout in which slot b owns
the blocks b * R / BS .. (b + 1) * R / BS - 1 for good: the one kernel reads
both kinds, the window layers through that fixed table with a `window`, which
starts it at the window's first page.

Prompts run as chunks in the decode step (`chunk_ladder`): a chunk's rows go
through every matmul and the experts with the slots' decode rows as one
batch, write their keys and values to the slot's blocks (full layers) and
ring (window layers), and attend both (`chunk_attention`, with the window
where the layer has one). `chunk_at` has three numbers: nothing resumes.

The decode step's first result is one int32 vector, fetched once a step: a
token a slot, then `COUNTERS` summed over the layers, then the chunk's three.
Its last is what a check reads (`PROBE`).
"""

from __future__ import annotations

import functools
from typing import Tuple

from ray_tpu.llm._engine import (  # chunk_ladder: the step set's own name
    chunk_ladder, feed_back, sample_tokens)
from ray_tpu.models import mellum
from ray_tpu.models.llama import rms_norm

# what a decode step counts on the device, in the order it returns them: the
# experts' four (`ling.moe_held`), then, each summed over the layers it is
# of: the positions the window layers' decode rows read, the keys a chunk's
# attention read and the query-key pairs it scored (window and full layers)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "window_positions", "chunk_keys_read",
            "chunk_pairs")
CACHE_NAMES = ("kc", "vc", "wk", "wv")
# the decode step's last result, by key: "routing" [layers, B, top_k + 1]
# (every slot's chosen experts and the always-kept one group) and, of slot
# `probe_slot` alone, "router_x" [layers, D], "router_s" [layers, n_experts]
# (the router's softmax) and "attn_o" [layers, heads, HD], every layer's
# attention before W_o; with a chunk also its rows' "chunk_routing"
# [layers, C, top_k + 1]
PROBE = ("routing", "router_x", "router_s", "attn_o", "chunk_routing")
# what a slot carries beside its blocks (a probed request reads its keys)
SLOT_STATE = "wk"
NO_PREFIX_CACHE = (
    "a full layer's blocks do not resume a window layer: its last "
    "sliding_window keys and values are the slot's own")
# a ring kept as a snapshot would share a prefix; nothing keeps one yet
SNAPSHOT_STATE = None
SNAPSHOT_POLICY = None


def _ring_positions(cfg: mellum.MellumConfig, ecfg) -> int:
    """R: the window and the widest chunk, rounded up to whole blocks."""
    bs = ecfg.kv_block_size
    return -(-(cfg.sliding_window + chunk_ladder(ecfg)[-1]) // bs) * bs


def alloc_cache(cfg: mellum.MellumConfig, ecfg) -> Tuple:
    import jax.numpy as jnp

    kc = jnp.zeros((cfg.full_layers, ecfg.num_kv_blocks + 1,
                    ecfg.kv_block_size, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype)
    wk = jnp.zeros((cfg.window_layers, ecfg.max_num_seqs,
                    _ring_positions(cfg, ecfg), cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype)
    return kc, jnp.zeros_like(kc), wk, jnp.zeros_like(wk)


def step_params(cfg: mellum.MellumConfig, params):
    """The decode step takes the weights as `mellum.init_params` lays them
    out."""
    return params


def make_kv_inject(cfg: mellum.MellumConfig, ecfg):
    raise ValueError(
        "transferred KV cannot seed a model with window layers: their keys "
        "and values are in the slot's ring, not in the blocks")


def extra_stats(cfg: mellum.MellumConfig, cache, attn_positions_live: int):
    kc, vc, wk, wv = cache
    return {"kv_bytes": int(kc.nbytes + vc.nbytes),
            # resident whatever the slots hold
            "window_bytes": int(wk.nbytes + wv.nbytes),
            # the keys (and values) the full layers' decode rows had to
            # read; the window layers' are the counter `window_positions`
            "kv_positions_live": attn_positions_live * cfg.full_layers}


def make_decode_step(cfg: mellum.MellumConfig, ecfg):
    """The jitted whole-batch single-token step that may also carry one
    chunk of one admitting prompt. Returns (step, path, note): which
    attention the decode rows were built with and, where a TPU was refused
    the kernel, why."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention

    bs = ecfg.kv_block_size
    max_blocks = -(-ecfg.max_model_len // bs)
    W, R = cfg.sliding_window, _ring_positions(cfg, ecfg)
    slots = ecfg.max_num_seqs
    # a slot's blocks of the rings seen as a pool: its own, for good
    ring_tables = np.arange(slots * (R // bs), dtype=np.int32).reshape(
        slots, R // bs)
    kinds = cfg.kinds()
    path, note = paged_attention.decode_path(
        cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs, cfg.dtype)

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(2, 3, 4, 5))
    def paged_decode_step(C, params, kc, vc, wk, wv, tables, lens, active,
                          last_tok, keys, temps, prev, fed, *chunk_and_probe):
        """The arguments of `llm/_engine`'s docstring; with a static chunk
        width C > 0 `chunk_ids` [C] and `chunk_at` [3] come before
        `probe_slot`."""
        dt = cfg.dtype
        B = last_tok.shape[0]
        probe_slot = chunk_and_probe[-1]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=True)
        ids, positions = last_tok, lens
        blk = jnp.clip(lens // bs, 0, max_blocks - 1)
        # inactive slots write into the reserved trash block 0 ...
        phys = jnp.where(active, tables[jnp.arange(B), blk], 0).astype(jnp.int32)
        off = (lens % bs).astype(jnp.int32)
        # ... and past their ring's end, which drops the write
        ring_slot = jnp.arange(B, dtype=jnp.int32)
        ring_at = jnp.where(active, lens % R, R).astype(jnp.int32)
        live = jnp.where(active, lens + 1, 0).astype(jnp.int32)
        rows_live = active
        if C:
            chunk_ids, chunk_at = chunk_and_probe[:2]
            slot, start, n = (chunk_at[i] for i in range(3))
            row = tables[slot]
            qpos = start + jnp.arange(C, dtype=jnp.int32)
            real = qpos < start + n
            ids = jnp.concatenate([ids, chunk_ids])
            positions = jnp.concatenate([positions, qpos])
            # padding writes into the trash block and past the ring's end
            phys = jnp.concatenate([phys, jnp.where(
                real, row[jnp.clip(qpos // bs, 0, max_blocks - 1)], 0)])
            off = jnp.concatenate([off, qpos % bs])
            ring_slot = jnp.concatenate(
                [ring_slot, jnp.full((C,), slot, jnp.int32)])
            ring_at = jnp.concatenate([ring_at, jnp.where(real, qpos % R, R)])
            rows_live = jnp.concatenate([active, real])
            ring_row = slot * (R // bs) + jnp.arange(R // bs, dtype=jnp.int32)
        h = params["tok_emb"].astype(dt)[ids]                     # [B + C, D]
        # what the attention reads, known before it runs: a window layer's
        # decode row its window, a chunk's row i of n min(start + i + 1, W)
        # keys in a window layer and start + i + 1 in a full one
        counters = jnp.zeros((len(COUNTERS),), jnp.int32).at[4].set(
            cfg.window_layers * jnp.sum(jnp.minimum(live, W)))
        if C:
            seen = jnp.where(real, qpos + 1, 0)
            counters = counters.at[5:7].set(
                cfg.full_layers * jnp.stack([start + n, jnp.sum(seen)])
                + cfg.window_layers * jnp.stack([
                    jnp.minimum(start + n, W + n - 1),
                    jnp.sum(jnp.minimum(seen, W))]))
        probe = {name: [] for name in PROBE}
        # the rings as a pool of blocks: the same bytes
        as_pool = (wk.shape[0], slots * (R // bs), bs) + wk.shape[3:]
        i_full = i_window = 0
        for kind, p in zip(kinds, params["layers"]):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            with jax.named_scope("attn_" + kind):
                q, k, v = mellum.attn_project(cfg, kind, p, x, positions)
                if kind == mellum.FULL:
                    kc = kc.at[i_full, phys, off].set(k)
                    vc = vc.at[i_full, phys, off].set(v)
                    o = paged_attention.decode_attention(
                        path, q[:B], kc, vc, i_full, tables, live)
                    if C:
                        o = jnp.concatenate([o, paged_attention.chunk_attention(
                            q[B:], kc, vc, i_full, row, qpos, start + n)])
                    i_full += 1
                else:
                    wk = wk.at[i_window, ring_slot, ring_at].set(k, mode="drop")
                    wv = wv.at[i_window, ring_slot, ring_at].set(v, mode="drop")
                    pool_k, pool_v = wk.reshape(as_pool), wv.reshape(as_pool)
                    o = paged_attention.decode_attention(
                        path, q[:B], pool_k, pool_v, i_window, ring_tables,
                        live, W)
                    if C:
                        o = jnp.concatenate([o, paged_attention.chunk_attention(
                            q[B:], pool_k, pool_v, i_window, ring_row, qpos,
                            start + n, window=W)])
                    i_window += 1
                y = mellum.attn_output(cfg, p, o)
            h = h + y
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            y, route, counts, scores = mellum.moe(cfg, p, x, rows_live)
            h = h + y
            counters = counters.at[:4].add(counts)
            probe["routing"].append(route[:B])
            probe["router_x"].append(x[probe_slot])
            probe["router_s"].append(scores[probe_slot])
            probe["attn_o"].append(o[probe_slot])
            if C:
                probe["chunk_routing"].append(route[B:])
        if C:
            # the rows whose logits are read: the decode rows and the
            # chunk's last real one, with the slot's own key and temperature
            h = jnp.concatenate([h[:B], h[B + jnp.clip(n - 1, 0, C - 1)][None]])
            keys = jnp.concatenate([keys, keys[slot][None]])
            temps = jnp.concatenate([temps, temps[slot][None]])
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        logits = (h @ params["lm_head"].astype(dt)).astype(jnp.float32)
        toks = sample_tokens(keys, logits, temps)
        if C:
            stream = jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(keys[B]), 7))
            out = jnp.concatenate(
                [toks[:B], counters, toks[B:],
                 jax.lax.bitcast_convert_type(stream, jnp.int32)])
        else:
            out = jnp.concatenate([toks, counters, jnp.zeros((3,), jnp.int32)])
        return (out, kc, vc, wk, wv,
                {name: jnp.stack(a) for name, a in probe.items() if a})

    return paged_decode_step, path, note

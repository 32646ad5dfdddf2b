"""The Solar-Open2 family's step set (`models/solar.py`): the jitted steps,
caches, counters and capabilities `PagedEngine` serves it by, under the names
of `llm/_engine.STEP_SET` (that module's docstring is the interface). What a
sequence has:

    kc, vc      [gqa_layers, NB + 1, BS, KV, HD]  keys and values of the
                softmax layers, paged under the engine's block table
    state       [kda_layers, slots, H, dk, dv] float32, one recurrent state a
                slot, and
    tails       [kda_layers, slots, K-1, 3*H*dk], the short convolution's
                last inputs: read and written in place by every step
    snap_state  [snapshots + 1, kda_layers, H, dk, dv] float32 and
    snap_tails  [snapshots + 1, kda_layers, K-1, 3*H*dk]: copies of a slot's
                state and tails as they stood at a block boundary of its
                prompt, which is what lets the prefix cache share the blocks
                before that boundary (`SNAPSHOT_STATE`). The engine's cache
                owns the entries' numbers; the last entry is a trash entry a
                step that takes no snapshot writes to.

Prompts run as chunks in the decode step (`chunk_ladder`): a chunk's rows go
through every matmul with the slots' decode rows as one batch; in a KDA
layer they run `ops/kda.kda_chunked` **from the slot's state** (zeros at
position 0, a snapshot the step copies in where the admission resumed from
one, else what the chunk before left) and leave the state and tail for the
next chunk or the first decode step; in a GQA layer their keys and values
are scattered into the slot's blocks and they attend the slot's table
(`ops/paged_attention.chunk_attention`), the decode rows through
`decode_attention` at 64 query heads on 8 KV heads. The chunk says where it
resumes (`chunk_at`, six numbers): slot, start, real tokens, the position
from which it writes keys and values (positions before it lie in shared,
cached blocks, and are run again only for the state), the snapshot to start
from (-1: none) and the entry to copy the slot's state to after the chunk
(the trash entry: none).

The decode step's first result is one int32 vector, fetched once a step: a
token a slot, then `COUNTERS` summed over the layers, then the chunk's three
(the token drawn from its last real row and the slot's stream key). Its last
is what a check reads (`PROBE`).
"""

from __future__ import annotations

import functools
from typing import Tuple

from ray_tpu.llm._engine import (  # chunk_ladder: the step set's own name
    chunk_ladder, feed_back, sample_tokens)
from ray_tpu.llm._prefix_cache import SnapshotsAtChunks
from ray_tpu.models import ling, solar
from ray_tpu.models.llama import rms_norm

# what a decode step counts on the device, in the order it returns them
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max")
CACHE_NAMES = ("kc", "vc", "state", "tails", "snap_state", "snap_tails")
# the decode step's last result, by key: "routing" [layers, B, top_k + 1]
# (every slot's chosen experts and the kept-groups mask) and, of slot
# `probe_slot` alone, "router_x" [layers, D], "router_s" [layers, n_experts]
# and the recurrence's inputs "q", "k", "v", "g" [kda_layers, H, dk] and
# "beta" [kda_layers, H]; with a chunk also its rows' "chunk_routing"
# [layers, C, top_k + 1] and "chunk_q", "chunk_k", "chunk_v", "chunk_g"
# [kda_layers, C, H, dk], "chunk_beta" [kda_layers, C, H]
PROBE = ("routing", "router_x", "router_s", "q", "k", "v", "g", "beta",
         "chunk_routing", "chunk_q", "chunk_k", "chunk_v", "chunk_g",
         "chunk_beta")
SLOT_STATE = "state"
# a matched run of blocks resumes a sequence only from a snapshot of the
# slot's state at or before its end: the prefix cache keeps them
NO_PREFIX_CACHE = None
SNAPSHOT_STATE = "snap_state"
# an entry is 12.7 MB beside a document's blocks: one a widest chunk
SNAPSHOT_POLICY = SnapshotsAtChunks


def alloc_cache(cfg: solar.SolarConfig, ecfg) -> Tuple:
    import jax.numpy as jnp

    B, H, dk = ecfg.max_num_seqs, cfg.kda_heads, cfg.kda_head_dim
    n_snap = ecfg.num_state_snapshots + 1
    tail = (cfg.conv_kernel - 1, cfg.conv_channels)
    kc = jnp.zeros((cfg.gqa_layers, ecfg.num_kv_blocks + 1,
                    ecfg.kv_block_size, cfg.n_kv_heads, cfg.head_dim),
                   cfg.dtype)
    return (kc, jnp.zeros_like(kc),
            jnp.zeros((cfg.kda_layers, B, H, dk, dk), jnp.float32),
            jnp.zeros((cfg.kda_layers, B) + tail, cfg.dtype),
            jnp.zeros((n_snap, cfg.kda_layers, H, dk, dk), jnp.float32),
            jnp.zeros((n_snap, cfg.kda_layers) + tail, cfg.dtype))


def step_params(cfg: solar.SolarConfig, params):
    """The decode step takes the weights as `solar.init_params` lays them
    out."""
    return params


def make_kv_inject(cfg: solar.SolarConfig, ecfg):
    raise ValueError(
        "transferred KV cannot seed a model with recurrent layers: its "
        "state is not in the blocks")


def extra_stats(cfg: solar.SolarConfig, cache, attn_positions_live: int):
    _, _, state, tails, snap_state, snap_tails = cache
    return {"state_bytes": int(state.nbytes + tails.nbytes),
            "snapshot_bytes": int(snap_state.nbytes + snap_tails.nbytes),
            # the keys (and values) a decode step's attention had to read
            "kv_positions_live": attn_positions_live * cfg.gqa_layers}


def make_decode_step(cfg: solar.SolarConfig, ecfg):
    """The jitted whole-batch single-token step that may also carry one
    chunk of one admitting prompt. Returns (step, path, note): which
    attention the decode rows were built with and, where a TPU was refused
    the kernel, why."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention

    bs = ecfg.kv_block_size
    max_blocks = -(-ecfg.max_model_len // bs)
    kinds = cfg.kinds()
    path, note = paged_attention.decode_path(
        cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, bs, cfg.dtype)

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(2, 3, 4, 5, 6, 7))
    def paged_decode_step(C, params, kc, vc, state, tails, snap_state,
                          snap_tails, tables, lens, active, last_tok, keys,
                          temps, prev, fed, *chunk_and_probe):
        """The arguments of `llm/_engine`'s docstring; with a static chunk
        width C > 0 `chunk_ids` [C] and `chunk_at` [6] (the module
        docstring) come before `probe_slot`."""
        dt = cfg.dtype
        B = last_tok.shape[0]
        probe_slot = chunk_and_probe[-1]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=True)
        ids = last_tok
        blk = jnp.clip(lens // bs, 0, max_blocks - 1)
        # inactive slots write into the reserved trash block 0
        phys = jnp.where(active, tables[jnp.arange(B), blk], 0).astype(jnp.int32)
        off = (lens % bs).astype(jnp.int32)
        live = jnp.where(active, lens + 1, 0).astype(jnp.int32)
        rows_live = active
        if C:
            chunk_ids, chunk_at = chunk_and_probe[:2]
            slot, start, n, write_from, restore, take = (
                chunk_at[i] for i in range(6))
            row = tables[slot]
            qpos = start + jnp.arange(C, dtype=jnp.int32)
            real = qpos < start + n
            ids = jnp.concatenate([ids, chunk_ids])
            # padding, and positions that lie in shared blocks, write into
            # the trash block
            phys = jnp.concatenate([phys, jnp.where(
                real & (qpos >= write_from),
                row[jnp.clip(qpos // bs, 0, max_blocks - 1)], 0)])
            off = jnp.concatenate([off, qpos % bs])
            rows_live = jnp.concatenate([active, real])
            # what the chunk's sequence carried to its first row
            at = jnp.clip(restore, 0, snap_state.shape[0] - 1)
            state_c = jnp.where(restore >= 0, snap_state[at], jnp.where(
                start == 0, 0.0, state[:, slot]))
            tails_c = jnp.where(restore >= 0, snap_tails[at], jnp.where(
                start == 0, jnp.zeros((), tails.dtype), tails[:, slot]))
        h = params["tok_emb"].astype(dt)[ids]                     # [B + C, D]
        counters = jnp.zeros((len(COUNTERS),), jnp.int32)
        probe = {name: [] for name in PROBE}
        i_kda = i_gqa = 0
        for kind, p in zip(kinds, params["layers"]):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            if kind == "kda":
                y, new, tail, new_c, tail_c, inputs = solar.kda_block(
                    cfg, p, x, state[i_kda], tails[i_kda],
                    (n, state_c[i_kda], tails_c[i_kda]) if C else None)
                # an idle slot keeps what it had: it may be between two
                # chunks of its prompt
                new = jnp.where(active[:, None, None, None], new, state[i_kda])
                tail = jnp.where(active[:, None, None], tail, tails[i_kda])
                if C:
                    new, tail = new.at[slot].set(new_c), tail.at[slot].set(tail_c)
                state = state.at[i_kda].set(new)
                tails = tails.at[i_kda].set(tail)
                for name, a in zip(("q", "k", "v", "g", "beta"), inputs):
                    probe[name].append(a[probe_slot])
                    if C:
                        probe["chunk_" + name].append(a[B:])
                i_kda += 1
            else:
                with jax.named_scope("gqa"):
                    q, k, v = solar.gqa_project(cfg, p, x)
                    kc = kc.at[i_gqa, phys, off].set(k)
                    vc = vc.at[i_gqa, phys, off].set(v)
                    o = paged_attention.decode_attention(
                        path, q[:B], kc, vc, i_gqa, tables, live)
                    if C:
                        o = jnp.concatenate([o, paged_attention.chunk_attention(
                            q[B:], kc, vc, i_gqa, row, qpos, start + n)])
                    y = solar.gqa_output(cfg, p, x, o)
                i_gqa += 1
            h = h + y
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            y, route, counts, scores = ling.moe_held(cfg, p, x, rows_live)
            h = h + y
            counters = counters + counts
            probe["routing"].append(route[:B])
            probe["router_x"].append(x[probe_slot])
            probe["router_s"].append(scores[probe_slot])
            if C:
                probe["chunk_routing"].append(route[B:])
        if C:
            # after the chunk's last layer: the slot's state as a later
            # prompt that shares the blocks up to here may resume from it
            snap_state = snap_state.at[take].set(state[:, slot])
            snap_tails = snap_tails.at[take].set(tails[:, slot])
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
        return (out, kc, vc, state, tails, snap_state, snap_tails,
                {name: jnp.stack(a) for name, a in probe.items() if a})

    return paged_decode_step, path, note

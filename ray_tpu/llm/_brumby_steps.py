"""The Brumby family's step set (`models/brumby.py`): the jitted steps, caches
and capabilities `PagedEngine` serves it by, under the names of
`llm/_engine.STEP_SET` (that module's docstring is the interface). The model
has no softmax layer, so **no array lies under the block table**: blocks are
the prefix cache's names for prefixes and stand for no device bytes
(`num_kv_blocks` sizes a list of integers; admission is bounded by the slots
and by `max_model_len`). What a sequence has:

    state       [layers, slots + 1, KV, W, hd] float32, one retention state a
                KV head (`ops/power_retention.py`: W = 8,704 at hd = 128,
                4.46 MB a head, 35.7 MB a layer), and
    norm        [layers, slots + 1, KV, hd, hd] float32, its normaliser: read
                and written in place by every step. The last slot is no
                sequence's: a slot that sits a step out is pointed there.
    snap_state  [snapshots + 1, layers, KV, W, hd] float32 and
    snap_norm   [snapshots + 1, layers, KV, hd, hd]: copies of a slot's state
                and normaliser as they stood at a block boundary of its
                prompt (`SNAPSHOT_STATE`), one entry 214 MB at the published
                widths and six layers. The engine's cache owns the entries'
                numbers; the last entry is a trash entry a step that takes no
                snapshot writes to. An entry costs what ~8,700 positions of
                float32 keys and values would, so one is taken only where
                prompts were seen to diverge (`SNAPSHOT_POLICY`).

Prompts run as chunks in the decode step (`chunk_ladder`): a chunk's rows go
through every matmul with the slots' decode rows as one batch and run
`retention_chunked` **from the slot's state** (zeros at position 0, a snapshot
the step copies in where the admission resumed from one, else what the chunk
before left). `chunk_at` has the six numbers of `llm/_engine`'s docstring;
the fourth (where keys and values are written from) has nothing to say here.

The decode step's first result is one int32 vector, fetched once a step: a
token a slot, then `COUNTERS`, then the chunk's three. Its last is what a
check reads (`PROBE`).
"""

from __future__ import annotations

import functools
from typing import Tuple

from ray_tpu.llm._engine import (  # chunk_ladder: the step set's own name
    chunk_ladder, feed_back, sample_tokens)
from ray_tpu.llm._prefix_cache import SnapshotsAtMatch
from ray_tpu.models import brumby
from ray_tpu.models.llama import rms_norm

# what a decode step counts on the device: the slots that decoded in it
COUNTERS = ("rows_decoded",)
CACHE_NAMES = ("state", "norm", "snap_state", "snap_norm")
# the decode step's last result, by key, of slot `probe_slot` alone: what
# its recurrence ran on, "k", "v" [layers, KV, hd], "gamma" [layers, KV], and
# the normed input its gate was computed from, "gate_x" [layers, D]; with a
# chunk also its rows' "chunk_k", "chunk_v" [layers, C, KV, hd],
# "chunk_gamma" [layers, C, KV] and "chunk_gate_x" [layers, C, D]
PROBE = ("k", "v", "gamma", "gate_x",
         "chunk_k", "chunk_v", "chunk_gamma", "chunk_gate_x")
SLOT_STATE = "state"
NO_PREFIX_CACHE = None
SNAPSHOT_STATE = "snap_state"
# an entry is the whole of a sequence's memory: taken where a match ended
# with none near, not along every prompt
SNAPSHOT_POLICY = SnapshotsAtMatch


def alloc_cache(cfg: brumby.BrumbyConfig, ecfg) -> Tuple:
    import jax.numpy as jnp

    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    slots, n_snap = ecfg.max_num_seqs + 1, ecfg.num_state_snapshots + 1
    return (jnp.zeros((L, slots, KV, cfg.state_width, hd), jnp.float32),
            jnp.zeros((L, slots, KV, hd, hd), jnp.float32),
            jnp.zeros((n_snap, L, KV, cfg.state_width, hd), jnp.float32),
            jnp.zeros((n_snap, L, KV, hd, hd), jnp.float32))


def step_params(cfg: brumby.BrumbyConfig, params):
    """The decode step takes the weights as `brumby.init_params` lays them
    out."""
    return params


def make_kv_inject(cfg: brumby.BrumbyConfig, ecfg):
    raise ValueError(
        "transferred KV cannot seed a model without keys and values: all a "
        "sequence has is its retention state")


def extra_stats(cfg: brumby.BrumbyConfig, cache, attn_positions_live: int):
    state, norm, snap_state, snap_norm = cache
    return {"state_bytes": int(state.nbytes + norm.nbytes),
            "snapshot_bytes": int(snap_state.nbytes + snap_norm.nbytes),
            # no step reads a key or a value by position
            "kv_positions_live": 0}


def make_decode_step(cfg: brumby.BrumbyConfig, ecfg):
    """The jitted whole-batch single-token step that may also carry one
    chunk of one admitting prompt. Returns (step, path, note): which
    `retention_step` the decode rows were built with (the Pallas kernel on a
    TPU, its XLA twin elsewhere)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as ret_ops

    path = ret_ops.step_path()
    eps = cfg.ret_eps

    @functools.partial(jax.jit, static_argnums=(0,),
                       donate_argnums=(2, 3, 4, 5))
    def paged_decode_step(C, params, state, norm, snap_state, snap_norm,
                          tables, lens, active, last_tok, keys, temps, prev,
                          fed, *chunk_and_probe):
        """The arguments of `llm/_engine`'s docstring; with a static chunk
        width C > 0 `chunk_ids` [C] and `chunk_at` [6] come before
        `probe_slot`. `tables` is taken and not read."""
        del tables
        dt = cfg.dtype
        B = last_tok.shape[0]
        probe_slot = chunk_and_probe[-1]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=True)
        ids, pos = last_tok, lens
        # a slot that sits the step out (idle, or between two chunks of its
        # prompt) keeps its state: its row moves the spare slot's
        at_slot = jnp.where(active, jnp.arange(B), B).astype(jnp.int32)
        if C:
            chunk_ids, chunk_at = chunk_and_probe[:2]
            slot, start, n, restore, take = (chunk_at[i] for i in (0, 1, 2, 4, 5))
            ids = jnp.concatenate([ids, chunk_ids])
            pos = jnp.concatenate([pos, start + jnp.arange(C, dtype=jnp.int32)])
            entry = jnp.clip(restore, 0, snap_state.shape[0] - 1)
        h = params["tok_emb"].astype(dt)[ids]                     # [B + C, D]

        def layer(carry, xs):
            # the caches ride in the carry and are written in place
            h, state, norm, snap_state, snap_norm = carry
            p, l = xs
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            moved = {}

            def step(q, k, v, gate):
                if path == ret_ops.KERNEL:
                    o, moved["S"], moved["Z"] = ret_ops.retention_step(
                        q, k, v, gate, state, norm, l, at_slot, eps)
                    return o
                o, S, Z = ret_ops.retention_step_xla(
                    q, k, v, gate, state[l, :B], norm[l, :B], eps)
                keep = active[:, None, None, None]
                moved["S"] = state.at[l, :B].set(
                    jnp.where(keep, S, state[l, :B]))
                moved["Z"] = norm.at[l, :B].set(
                    jnp.where(keep, Z, norm[l, :B]))
                return o

            chunk = None
            if C:
                # what the chunk's sequence carried to its first row
                S0 = jnp.where(restore >= 0, snap_state[entry, l], jnp.where(
                    start == 0, 0.0, state[l, slot]))
                Z0 = jnp.where(restore >= 0, snap_norm[entry, l], jnp.where(
                    start == 0, 0.0, norm[l, slot]))
                chunk = (n, S0, Z0)
            y, S_c, Z_c, inputs = brumby.ret_block(
                cfg, p, x, pos, B, step, chunk)
            state, norm = moved["S"], moved["Z"]
            probe = {"gate_x": x[probe_slot]}
            for name, a in zip(("k", "v", "gamma"), inputs[1:]):
                probe[name] = a[probe_slot]
            if C:
                state = state.at[l, slot].set(S_c)
                norm = norm.at[l, slot].set(Z_c)
                # the slot's state as a later prompt that shares the blocks
                # up to here may resume from it
                snap_state = snap_state.at[take, l].set(S_c)
                snap_norm = snap_norm.at[take, l].set(Z_c)
                probe["chunk_gate_x"] = x[B:]
                for name, a in zip(("k", "v", "gamma"), inputs[1:]):
                    probe["chunk_" + name] = a[B:]
            h = h + y
            h = h + brumby.ffn(cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps))
            return (h, state, norm, snap_state, snap_norm), probe

        (h, state, norm, snap_state, snap_norm), probe = jax.lax.scan(
            layer, (h, state, norm, snap_state, snap_norm),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        if C:
            # the rows whose logits are read: the decode rows and the
            # chunk's last real one, with the slot's own key and temperature
            h = jnp.concatenate([h[:B], h[B + jnp.clip(n - 1, 0, C - 1)][None]])
            keys = jnp.concatenate([keys, keys[slot][None]])
            temps = jnp.concatenate([temps, temps[slot][None]])
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        logits = (h @ params["lm_head"].astype(dt)).astype(jnp.float32)
        toks = sample_tokens(keys, logits, temps)
        rows = jnp.sum(active, dtype=jnp.int32)[None]
        if C:
            stream = jax.random.key_data(jax.random.fold_in(
                jax.random.wrap_key_data(keys[B]), 7))
            out = jnp.concatenate(
                [toks[:B], rows, toks[B:],
                 jax.lax.bitcast_convert_type(stream, jnp.int32)])
        else:
            out = jnp.concatenate([toks, rows, jnp.zeros((3,), jnp.int32)])
        return out, state, norm, snap_state, snap_norm, probe

    return paged_decode_step, path, None

"""The JoyAI-LLM-Flash family's step set (`models/joyai.py`): the jitted
step, cache, counters and capabilities `PagedEngine` serves it by, under the
names of `llm/_engine.STEP_SET` (that module's docstring is the interface).
A sequence's only memory is its latents:

    latents  [n_layers, NB + 1, BS, 1, W]   paged under the engine's block
             table and allocator: the KV pool's layout with one "head" a
             token, whose key is the latent (rank + rope = 576 values, W =
             640 with the lanes' padding) and whose value is its first `rank`

so a block alone resumes a sequence: the prefix cache shares blocks as it
does the Llama family's (`NO_PREFIX_CACHE` None, no slot state, no
snapshots), and transferred blocks seed a sequence (`make_kv_inject`).

Prompts run as chunks in the decode step (`chunk_ladder`): a chunk's rows go
through every matmul and the experts with the slots' decode rows as one
batch, write their latents to the slot's blocks and attend them in the
absorbed form (`ops/paged_attention.chunk_latent_attention`): over blocks
another sequence's prompt left, earlier chunks and their own rows alike. The
decode rows attend through `ops/paged_attention.paged_latent_attention` on a
TPU: the kernel over the pool as its one operand, a live page fetched once
and its first `rank` columns the value. The leading dense layers are
unrolled; the expert layers are one `lax.scan` over their stacked weights,
the layer's number traced into the whole pool, which rides in the carry and
is written in place (`_engine._make_decode_step`).

The decode step's first result is one int32 vector, fetched once a step: a
token a slot, then `COUNTERS` summed over the layers, then the chunk's three.
Its last is what a check reads (`PROBE`).
"""

from __future__ import annotations

import functools
from typing import Tuple

from ray_tpu.llm._engine import (  # chunk_ladder: the step set's own name
    chunk_ladder, feed_back, sample_tokens)
from ray_tpu.models import joyai, ling
from ray_tpu.models.llama import rms_norm

# what a decode step counts on the device, in the order it returns them: the
# experts' four (`ling.moe_held`), then, each summed over the layers: the
# latents the decode rows' attention had to read (a row's live length) and
# the positions a chunk's attention covered (up to its end)
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max", "latent_positions_read", "chunk_latents_read")
CACHE_NAMES = ("latents",)
_EXPERTS = ("e_w1", "e_w3", "e_w2")      # the held experts' leaves
# the decode step's last result, by key: "routing" [moe_layers, B, top_k + 1]
# (every slot's chosen experts and the one group) and, of slot `probe_slot`
# alone, "router_x" [moe_layers, D] (the router's input, dtype) and
# "router_s" [moe_layers, n_experts] (its scores, float32); with a chunk
# also its rows' "chunk_routing" [moe_layers, C, top_k + 1]
PROBE = ("routing", "router_x", "router_s", "chunk_routing")
# the blocks are all a sequence has
SLOT_STATE = None
NO_PREFIX_CACHE = None
SNAPSHOT_STATE = None
SNAPSHOT_POLICY = None


def alloc_cache(cfg: joyai.JoyAIConfig, ecfg) -> Tuple:
    import jax.numpy as jnp

    return (jnp.zeros((cfg.n_layers, ecfg.num_kv_blocks + 1,
                       ecfg.kv_block_size, 1, cfg.latent_width), cfg.dtype),)


def step_params(cfg: joyai.JoyAIConfig, params):
    """The decode step takes the weights as `joyai.init_params` lays them
    out."""
    return params


def make_kv_inject(cfg: joyai.JoyAIConfig, ecfg):
    """Blocks `phys` of the pool take the latents another worker's prompt
    pass computed, [n_layers, nb, BS, 1, W]: as `_engine._make_kv_inject`
    seeds keys and values, in one array."""
    import jax

    def paged_kv_inject(latents, phys, blocks):
        return (latents.at[:, phys].set(blocks),)

    return jax.jit(paged_kv_inject, donate_argnums=(0,))


def extra_stats(cfg: joyai.JoyAIConfig, cache, attn_positions_live: int):
    return {"latent_bytes": int(cache[0].nbytes),
            # the latents a decode step's attention had to read, summed (the
            # host's count of what `latent_positions_read` counts on the
            # device)
            "latent_positions_live": attn_positions_live * cfg.n_layers}


def make_decode_step(cfg: joyai.JoyAIConfig, ecfg):
    """The jitted whole-batch single-token step that may also carry one
    chunk of one admitting prompt. Returns (step, path, note): which
    attention the decode rows were built with and, where a TPU was refused
    the kernel, why."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention

    bs = ecfg.kv_block_size
    max_blocks = -(-ecfg.max_model_len // bs)
    W, rank = cfg.latent_width, cfg.kv_lora_rank
    n_dense = cfg.first_k_dense
    path, note = paged_attention.decode_path(cfg.n_heads, 1, W, bs, cfg.dtype)

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    def paged_decode_step(C, params, latents, tables, lens, active, last_tok,
                          keys, temps, prev, fed, *chunk_and_probe):
        """The arguments of `llm/_engine`'s docstring; with a static chunk
        width C > 0 `chunk_ids` [C] and `chunk_at` [3] come before
        `probe_slot`."""
        dt = cfg.dtype
        B = last_tok.shape[0]
        probe_slot = chunk_and_probe[-1]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=True)
        ids, positions = last_tok, lens
        blk = jnp.clip(lens // bs, 0, max_blocks - 1)
        # inactive slots write into the reserved trash block 0
        phys = jnp.where(active, tables[jnp.arange(B), blk], 0).astype(jnp.int32)
        off = (lens % bs).astype(jnp.int32)
        live = jnp.where(active, lens + 1, 0).astype(jnp.int32)
        rows_live = active
        counters = jnp.zeros((len(COUNTERS),), jnp.int32).at[4].set(
            cfg.n_layers * jnp.sum(live))
        if C:
            chunk_ids, chunk_at = chunk_and_probe[:2]
            slot, start, n = (chunk_at[i] for i in range(3))
            row = tables[slot]
            qpos = start + jnp.arange(C, dtype=jnp.int32)
            real = qpos < start + n
            ids = jnp.concatenate([ids, chunk_ids])
            positions = jnp.concatenate([positions, qpos])
            # padding writes into the trash block
            phys = jnp.concatenate([phys, jnp.where(
                real, row[jnp.clip(qpos // bs, 0, max_blocks - 1)], 0)])
            off = jnp.concatenate([off, qpos % bs])
            rows_live = jnp.concatenate([active, real])
            counters = counters.at[5].set(cfg.n_layers * (start + n))
        h = params["tok_emb"].astype(dt)[ids]                     # [B + C, D]

        def attention(latents, layer):
            """`ling.mla_decode`'s `attend` over layer `layer` of the pool:
            the decode rows each over their slot's live latents (the paged
            kernel, which reads the pool once; a gathered context off-TPU),
            the chunk's rows over their sequence's up to their own
            positions."""
            def attend(q, scale):
                if path == paged_attention.KERNEL:
                    o = paged_attention.paged_latent_attention(
                        q[:B], scale, latents, layer, tables, live, rank)
                else:
                    context = latents[layer][tables].reshape(
                        B, max_blocks * bs, W)
                    o = ling.attend_latents(cfg, context, live)(q[:B], scale)
                if C:
                    qc = jnp.pad(q[B:] * jnp.asarray(scale, q.dtype),
                                 ((0, 0), (0, 0), (0, W - q.shape[-1])))
                    o = jnp.concatenate([
                        o, paged_attention.chunk_latent_attention(
                            qc, latents, layer, row, qpos, start + n, rank)])
                return o

            return attend

        def block(h, latents, p, layer):
            """One layer on the step's rows: (h, latents, the router's input,
            `ling.ffn`'s routing, counters and scores)."""
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            with jax.named_scope("mla"):
                lat = ling.mla_latents(cfg, p, x, positions)
                latents = latents.at[layer, phys, off, 0].set(
                    jnp.pad(lat, ((0, 0), (0, W - lat.shape[1]))))
            h = h + ling.mla_decode(cfg, p, x, positions,
                                    attention(latents, layer))
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            y, route, counts, scores = ling.ffn(cfg, p, x, rows_live)
            return h + y, latents, x, route, counts, scores

        for i, p in enumerate(params["dense"]):
            h, latents, *_ = block(h, latents, p, i)

        # the held experts stay whole, all layers' on one leading axis: as
        # the scan's xs each layer's 150 MB would be copied out of the stack
        # for the grouped kernel, a fifth of a step's device time (PERF.md
        # section 6, PR 60)
        stack = params["layers"]
        experts = {k: stack[k].reshape((-1,) + stack[k].shape[2:])
                   for k in _EXPERTS}

        def layer(carry, xs):
            h, latents, counters = carry
            p, l = xs
            p = {**p, **experts, "e_first": (l - n_dense) * cfg.n_held}
            h, latents, x, route, counts, scores = block(h, latents, p, l)
            probe = {"routing": route[:B], "router_x": x[probe_slot],
                     "router_s": scores[probe_slot]}
            if C:
                probe["chunk_routing"] = route[B:]
            return (h, latents, counters.at[:4].add(counts)), probe

        (h, latents, counters), probe = jax.lax.scan(
            layer, (h, latents, counters),
            ({k: v for k, v in stack.items() if k not in _EXPERTS},
             n_dense + jnp.arange(cfg.moe_layers, dtype=jnp.int32)))
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
        return out, latents, probe

    return paged_decode_step, path, note

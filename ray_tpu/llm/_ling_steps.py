"""The Ling family's step set (`models/ling.py`): the jitted steps, caches,
counters and capabilities `PagedEngine` serves it by, under the names of
`llm/_engine.STEP_SET` (that module's docstring is the interface). Three
kinds of per-sequence state:

    latents  [mla_layers, NB, BS, 1, W]   paged under the engine's block
             table and allocator: the KV pool's layout with one "head" a
             token, whose key is the latent (rank + rope = 576 values, W =
             640 with the lanes' padding, `LingConfig.latent_width`) and
             whose value is its first `rank`
    state    [kda_layers, slots, H, dk, dv] float32, one recurrent state a
             slot: written whole by the slot's prefill (which starts from
             zeros, so a reused slot carries nothing over), read and written
             in place by every decode step, never paged
    tails    [kda_layers, slots, K-1, 3*H*dk]: the short convolution's last
             inputs, kept with the state

All three are donated to each step and returned by it; the weights every
step takes are the given tree (`step_params` is the identity). The steps are
named `paged_decode_step` and `paged_prefill` as every family's are, so the
device trace's `jit_paged_*` programs mean the same whatever is served. The
decode step takes no chunk (a chunk would have to hand the KDA state across
steps): prompts run whole through `make_prefill`, awaited in the engine's
loop.

The decode step's first result is one int32 vector, fetched once a step: the
sampled tokens [B], then `COUNTERS` summed over the expert layers. Its last
is what a check reads and the loop leaves on the device unless a request
asked (`PagedEngine.check_routing`): every expert layer's routing, and for
the one slot `probe_slot` names the router's input and scores and the
recurrence's inputs, so that a reference can hold the router and the state
to the same inputs (`PROBE`).
"""

from __future__ import annotations

import functools
from typing import Tuple

from ray_tpu.models import ling
from ray_tpu.models.llama import rms_norm

# what a decode step counts on the device, in the order it returns them
COUNTERS = ("moe_pairs_routed", "moe_pairs_held", "moe_experts_touched",
            "moe_load_max")
CACHE_NAMES = ("latents", "state", "tails")
# the decode step's last result, by key: "routing" [moe_layers, B, top_k + 1]
# (chosen experts and kept-groups mask, every slot) and, of slot `probe_slot`
# alone, "router_x" [moe_layers, D] (the router's input, dtype), "router_s"
# [moe_layers, n_experts] (its scores, float32) and the recurrence's inputs
# "q", "k", "v", "g" [kda_layers, H, dk] and "beta" [kda_layers, H] (float32)
PROBE = ("routing", "router_x", "router_s", "q", "k", "v", "g", "beta")
# a slot's recurrent state is not in its blocks: a new request's prefill
# starts it from zeros, and neither a shared block nor a transferred one can
# resume a sequence
SLOT_STATE = "state"
NO_PREFIX_CACHE = (
    "prefix_cache=True with recurrent layers: a shared block of latents "
    "would need the recurrent state at its boundary, and nothing snapshots "
    "that state")
SNAPSHOT_STATE = None
SNAPSHOT_POLICY = None


def alloc_cache(cfg: ling.LingConfig, ecfg) -> Tuple:
    import jax.numpy as jnp

    B, H, dk = ecfg.max_num_seqs, cfg.n_heads, cfg.head_dim
    return (
        jnp.zeros((cfg.mla_layers, ecfg.num_kv_blocks + 1,
                   ecfg.kv_block_size, 1, cfg.latent_width), cfg.dtype),
        jnp.zeros((cfg.kda_layers, B, H, dk, dk), jnp.float32),
        jnp.zeros((cfg.kda_layers, B, cfg.conv_kernel - 1, cfg.conv_channels),
                  cfg.dtype))


def step_params(cfg: ling.LingConfig, params):
    """The decode step takes the weights as `ling.init_params` lays them
    out."""
    return params


def chunk_ladder(ecfg) -> Tuple[int, ...]:
    return ()


def make_kv_inject(cfg: ling.LingConfig, ecfg):
    raise ValueError(
        "transferred KV cannot seed a model with recurrent layers: its "
        "state is not in the blocks")


def extra_stats(cfg: ling.LingConfig, cache, attn_positions_live: int):
    _, state, tails = cache
    return {"state_bytes": int(state.nbytes + tails.nbytes),
            # the latents a decode step's attention had to read, summed
            "latent_positions_live": attn_positions_live * cfg.mla_layers}


def make_decode_step(cfg: ling.LingConfig, ecfg):
    """The jitted whole-batch single-token step. Returns (step, path, note):
    which latent attention it was built with and, where a TPU was refused
    the kernel, why. On a TPU the latent attention is
    `ops/paged_attention.paged_latent_attention`: the kernel over the block
    table with the pool as its one operand, a live page fetched once and
    its first `kv_lora_rank` columns the value; elsewhere an XLA gather of
    the table's blocks."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm._engine import feed_back, sample_tokens
    from ray_tpu.ops import paged_attention

    bs = ecfg.kv_block_size
    max_blocks = -(-ecfg.max_model_len // bs)
    kinds = cfg.kinds()
    W = cfg.latent_width
    path, note = paged_attention.decode_path(cfg.n_heads, 1, W, bs, cfg.dtype)

    def attention(latents, layer, tables, live):
        if path != paged_attention.KERNEL:
            context = latents[layer][tables].reshape(
                tables.shape[0], max_blocks * bs, W)
            return ling.attend_latents(cfg, context, live)

        return lambda q, scale: paged_attention.paged_latent_attention(
            q, scale, latents, layer, tables, live, cfg.kv_lora_rank)

    def paged_decode_step(params, latents, state, tails, tables, lens, active,
                          last_tok, keys, temps, prev, fed, probe_slot):
        dt = cfg.dtype
        B = last_tok.shape[0]
        last_tok, keys = feed_back(prev, fed, last_tok, keys, chunked=False)
        h = params["tok_emb"].astype(dt)[last_tok]               # [B, D]
        blk = jnp.clip(lens // bs, 0, max_blocks - 1)
        # inactive slots write into the reserved trash block 0
        phys = jnp.where(active, tables[jnp.arange(B), blk], 0).astype(jnp.int32)
        off = (lens % bs).astype(jnp.int32)
        live = jnp.where(active, lens + 1, 0).astype(jnp.int32)
        counters = jnp.zeros((len(COUNTERS),), jnp.int32)
        probe = {name: [] for name in PROBE}
        i_kda = i_mla = 0
        for (attn, _), p in zip(kinds, params["layers"]):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            if attn == "kda":
                y, new, tail, inputs = ling.kda_decode(
                    cfg, p, x, state[i_kda], tails[i_kda])
                # an empty slot keeps what it had: its next prefill overwrites it
                keep = active[:, None, None, None]
                state = state.at[i_kda].set(jnp.where(keep, new, state[i_kda]))
                tails = tails.at[i_kda].set(tail)
                for name, a in zip(("q", "k", "v", "g", "beta"), inputs):
                    probe[name].append(a[probe_slot])
                i_kda += 1
            else:
                with jax.named_scope("mla"):
                    lat = ling.mla_latents(cfg, p, x, lens)
                    latents = latents.at[i_mla, phys, off, 0].set(
                        jnp.pad(lat, ((0, 0), (0, W - lat.shape[1]))))
                    attend = attention(latents, i_mla, tables, live)
                y = ling.mla_decode(cfg, p, x, lens, attend)
                i_mla += 1
            h = h + y
            x = rms_norm(h, p["ln2"], cfg.norm_eps)
            y, route, counts, scores = ling.ffn(cfg, p, x, active)
            h = h + y
            if route is not None:
                counters = counters + counts
                probe["routing"].append(route)
                probe["router_x"].append(x[probe_slot])
                probe["router_s"].append(scores[probe_slot])
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        logits = (h @ params["lm_head"].astype(dt)).astype(jnp.float32)
        out = jnp.concatenate([sample_tokens(keys, logits, temps), counters])
        return (out, latents, state, tails,
                {name: jnp.stack(a) for name, a in probe.items() if a})

    return jax.jit(paged_decode_step, donate_argnums=(1, 2, 3)), path, note


def make_prefill(cfg: ling.LingConfig, ecfg):
    """Jitted single-request prefill at a static padded length S: the KDA
    layers as a chunked scan from a zero state, the MLA layers expanded;
    writes the slot's state and tails and the latents of its blocks.
    Returns (last logits, routing [moe_layers, S, top_k + 1], caches)."""
    import jax
    import jax.numpy as jnp

    bs = ecfg.kv_block_size
    kinds = cfg.kinds()

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2, 3, 4))
    def paged_prefill(S, params, latents, state, tails, table, prompt, plen,
                      slot):
        dt = cfg.dtype
        idx = jnp.arange(S)
        valid = idx < plen
        phys = jnp.where(valid, table[jnp.clip(idx // bs, 0,
                                               table.shape[0] - 1)], 0)
        off = (idx % bs).astype(jnp.int32)
        h = params["tok_emb"].astype(dt)[prompt]                 # [S, D]
        routing = []
        i_kda = i_mla = 0
        for (attn, _), p in zip(kinds, params["layers"]):
            x = rms_norm(h, p["ln1"], cfg.norm_eps)
            if attn == "kda":
                y, final, tail = ling.kda_prefill(cfg, p, x, valid)
                state = state.at[i_kda, slot].set(final)
                tails = tails.at[i_kda, slot].set(tail)
                i_kda += 1
            else:
                y, lat = ling.mla_prefill(cfg, p, x, valid)
                with jax.named_scope("mla"):
                    latents = latents.at[i_mla, phys, off, 0].set(jnp.pad(
                        lat, ((0, 0), (0, cfg.latent_width - lat.shape[1]))))
                i_mla += 1
            h = h + y
            y, route, _, _ = ling.ffn(
                cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps), valid)
            h = h + y
            if route is not None:
                routing.append(route)
        h = rms_norm(h, params["norm"], cfg.norm_eps)
        last = h[jnp.clip(plen - 1, 0, S - 1)]
        logits = (last @ params["lm_head"].astype(dt)).astype(jnp.float32)
        routing = (jnp.stack(routing) if routing
                   else jnp.zeros((0, S, cfg.top_k + 1), jnp.int32))
        return logits, routing, latents, state, tails

    return paged_prefill


def check_prefill(cfg: ling.LingConfig, ecfg, prefill, params, prompt_ids):
    """The jitted `prefill` on caches of its own (one slot, the prompt's
    blocks) against `ling.forward` on the same prompt: (last logits of the
    step, of the forward pass)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    p = list(prompt_ids) or [0]
    plen = len(p)
    nb = -(-plen // ecfg.kv_block_size)
    S = max(8, 1 << (plen - 1).bit_length())
    caches = alloc_cache(cfg, dataclasses.replace(
        ecfg, max_num_seqs=1, num_kv_blocks=nb))
    prompt = np.zeros((S,), np.int32)
    prompt[:plen] = p
    got = prefill(S, params, *caches, jnp.arange(1, nb + 1, dtype=jnp.int32),
                  jnp.asarray(prompt), jnp.int32(plen), jnp.int32(0))[0]
    ref = jax.jit(functools.partial(ling.forward, cfg))(
        params, jnp.asarray(prompt), jnp.int32(plen))[plen - 1]
    return got, ref

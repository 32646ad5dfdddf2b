"""The plain reference: a pre-norm decoder written from the published
description, in straightforward float32 `jax.numpy`.

It shares no code with `ray_tpu`: no kernel, no cache, no batching, no
sharding. It is what the system's answers are held against, on the chip at
the published widths and in the CPU tests at a tiny size.

Architecture (MistralForCausalLM and InternLM2ForCausalLM on Hugging Face
describe the same block): token embedding; per layer RMSNorm -> grouped-query
causal self-attention with rotary position embedding -> residual, RMSNorm ->
SwiGLU feed-forward -> residual; final RMSNorm; an output head that is not
tied to the embedding.

Departures from the checkpoints, none of which changes the mathematics:
- a weight is stored [in, out] and applied as `x @ w` (the checkpoints store
  [out, in]);
- InternLM2 fuses q, k and v into one `wqkv`; here they are three matrices;
- rotary embedding in the Hugging Face convention: the two halves of a head
  are rotated against each other (not interleaved pairs).

`hp` is a configuration file's dict with the published keys. Weights arrive
through a small view object so that a model larger than the free memory can
be walked one layer at a time:

    weights.embed(tokens) -> [T, D] float32 rows of the embedding table
    weights.layer(i)      -> dict of float32 arrays: attn_norm [D], wq [D, H*hd],
                             wk, wv [D, KV*hd], wo [H*hd, D], ffn_norm [D],
                             w_gate, w_up [D, F], w_down [F, D]
    weights.final_norm()  -> [D]
    weights.head()        -> [D, V]

On a TPU a float32 matmul runs in lower precision unless
`jax.default_matmul_precision("highest")` is set; every entry point here sets
it.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512  # attention is computed in query blocks so scores fit


def _hd(hp: dict) -> int:
    return hp.get("head_dim") or hp["hidden_size"] // hp["num_attention_heads"]


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, heads, hd]; positions [T]. Hugging Face `rotate_half` form:
    out = x * cos + rotate_half(x) * sin, frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # [T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v):
    """q [T, H, hd], k/v [T, KV, hd] -> [T, H, hd]. Each query position sees
    itself and everything before it; a group of H/KV query heads shares one
    key/value head."""
    T, H, hd = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kpos = jnp.arange(T)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        qpos = start + jnp.arange(qb.shape[0])
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(out, axis=0)


def layer(hp: dict, h, w: Dict[str, Any], positions):
    """One decoder layer on one sequence: h [T, D] -> [T, D]."""
    T = h.shape[0]
    H, KV, hd = hp["num_attention_heads"], hp["num_key_value_heads"], _hd(hp)
    x = rms_norm(h, w["attn_norm"], hp["rms_norm_eps"])
    q = rope((x @ w["wq"]).reshape(T, H, hd), positions, hp["rope_theta"])
    k = rope((x @ w["wk"]).reshape(T, KV, hd), positions, hp["rope_theta"])
    v = (x @ w["wv"]).reshape(T, KV, hd)
    h = h + causal_attention(q, k, v).reshape(T, H * hd) @ w["wo"]
    x = rms_norm(h, w["ffn_norm"], hp["rms_norm_eps"])
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


@functools.lru_cache(maxsize=None)
def _jitted_layer(hp_items: tuple):
    return jax.jit(functools.partial(layer, dict(hp_items)))


def _layer_fn(hp: dict):
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "rms_norm_eps", "rope_theta", "head_dim")
    return _jitted_layer(tuple((k, hp[k]) for k in keys if k in hp))


def hidden_states(hp: dict, weights, tokens: Sequence[int],
                  keep_inputs: bool = False):
    """Final-norm input h [T, D] after every layer (and, with `keep_inputs`,
    the input of each layer, for the layer-by-layer backward pass)."""
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    fn = _layer_fn(hp)
    inputs: List[Any] = []
    with jax.default_matmul_precision("highest"):
        h = weights.embed(tokens).astype(jnp.float32)
        for i in range(hp["num_hidden_layers"]):
            if keep_inputs:
                inputs.append(h)
            h = fn(h, weights.layer(i), positions)
    return (h, inputs) if keep_inputs else h


def logits_at(hp: dict, weights, tokens: Sequence[int],
              positions: Sequence[int]) -> np.ndarray:
    """Float32 logits [len(positions), V] of the full forward pass over
    `tokens`, at the given positions only."""
    h = hidden_states(hp, weights, tokens)
    with jax.default_matmul_precision("highest"):
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        rows = rms_norm(rows, weights.final_norm(), hp["rms_norm_eps"])
        return np.asarray(rows @ weights.head())


def pad_to_multiple(tokens: Sequence[int], multiple: int) -> List[int]:
    """Right-pad with zeros so that few distinct lengths are compiled. Under
    a causal mask a position never sees what follows it, so padding changes
    no logit at a real position."""
    n = -(-len(tokens) // multiple) * multiple
    return list(tokens) + [0] * (n - len(tokens))


# --- the serve check ------------------------------------------------------


def teacher_forced_gaps(hp: dict, weights, prompt: Sequence[int],
                        answer: Sequence[int], pad_multiple: int = 256
                        ) -> Dict[str, Any]:
    """Feed prompt + answer through the reference and, at each answer
    position, measure how far the returned token's reference logit lies
    under that position's largest (0 = the reference picks the same token).

    Logits and not tokens are compared: with random weights the largest
    logit changes on rounding, and a near-tie is not an error."""
    seq = list(prompt) + list(answer)
    at = [len(prompt) - 1 + i for i in range(len(answer))]
    lg = logits_at(hp, weights, pad_to_multiple(seq, pad_multiple), at)
    top = lg.max(axis=-1)
    got = lg[np.arange(len(answer)), np.asarray(answer)]
    return {"gaps": (top - got).tolist(),
            "max_abs_logit": float(np.abs(lg).max()),
            "argmax_equal": int((lg.argmax(-1) == np.asarray(answer)).sum())}


# --- the train check ------------------------------------------------------


def _nll(hp, h, final_norm, head, targets):
    x = rms_norm(h[:-1], final_norm, hp["rms_norm_eps"])
    logp = jax.nn.log_softmax(x @ head, axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=-1).mean()


def loss_and_grad_norm(hp: dict, weights, tokens: Sequence[int]
                       ) -> Dict[str, float]:
    """Next-token cross entropy of one sequence (mean over its T-1 targets)
    and the global L2 norm of its gradient over every parameter, walked one
    layer at a time so that only one layer's weights and gradients are
    alive at once."""
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    positions = jnp.arange(toks.shape[0], dtype=jnp.int32)
    h, inputs = hidden_states(hp, weights, tokens, keep_inputs=True)
    fn = _layer_fn(hp)
    with jax.default_matmul_precision("highest"):
        loss, vjp = jax.vjp(
            functools.partial(_nll, hp, targets=toks[1:]),
            h, weights.final_norm(), weights.head())
        g_h, g_norm, g_head = vjp(jnp.float32(1.0))
        sq = float(jnp.sum(g_norm ** 2) + jnp.sum(g_head ** 2))
        del g_head
        for i in reversed(range(hp["num_hidden_layers"])):
            _, vjp = jax.vjp(lambda hh, ww: fn(hh, ww, positions),
                             inputs.pop(), weights.layer(i))
            g_h, g_w = vjp(g_h)
            sq += float(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(g_w)))
            del g_w
        # the embedding's gradient: rows of g_h summed per token id
        g_emb = jax.ops.segment_sum(g_h, toks, num_segments=hp["vocab_size"])
        sq += float(jnp.sum(g_emb ** 2))
    return {"loss": float(loss), "grad_norm": float(np.sqrt(sq))}

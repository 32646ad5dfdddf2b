"""The plain reference of Solar-Open2: the forward pass written from the
published configuration, in straightforward float32 `jax.numpy`, one sequence
at a time from position 0. It shares no code with `ray_tpu`: no kernel, no
cache, no chunk, no snapshot, no batching. What it shares with
lib/reference_ling.py (the benchmark's own) it imports from there: the
token-by-token delta rule, the held experts in blocks, the routing margins
and the float32 readings of router and state.

Published layer l (pre-norm residual, eps from the config, no bias anywhere):

    h += Attn_l(RMSNorm(h));  h += MoE(RMSNorm(h))

`Attn_l` is GQA where l % (gqa_interval + 1) == 0 (`gqa_layers`), KDA
elsewhere; every layer has experts (`first_k_dense_replace` 0). A
configuration that keeps some of the published layers names them in
`layer_ids`.

GQA, no positions (`use_rope` false):
    q = W_q x -> heads x hd;  k, v = W_k x, W_v x -> kv_heads x hd
    causal softmax(q k^T / sqrt(hd)) v, query head h on KV head h // group
    o <- o * sigmoid(W_g x) element-wise (`use_gqa_gate`);  out = W_o o
KDA (`linear_attn_config`: H heads of dk, conv K):
    q, k, v = conv_silu(W_q x), conv_silu(W_k x), conv_silu(W_v x)
    q, k L2-normalised per head, q scaled by dk^-1/2
    g_t = -exp(A_log_h) softplus(W_up (W_down x) + dt_bias)   per channel
    beta_t = 2 sigmoid(W_beta x)                              per head
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;  out = W_o (RMSNorm_head(o_t) * sigmoid(W_g,up (W_g,down x)))
MoE: s = sigmoid(W_r x) in float32; top-k of s + b over all experts (no
groups); weights s_i / sum s_j * routed_scaling_factor; y = sum_{chosen and
held} w_i E_i(x) + E_shared(x). Only the experts [held_start, held_start +
n_routed_experts) are held, as in the program (model-configs guide,
section 4).

What the published keys do not settle is listed in the configuration file's
`assumed`. `hp` is the configuration file's dict with the program section's
`layer_ids`, `router_num_experts` and `held_experts_start` beside it
(runners/serve_dp_solar.reference_hp). Weights arrive through a view object
(runners/_inside_solar.ProgramWeightsSolar, or a test's own):

    weights.embed(tokens) -> [T, D];  weights.final_norm();  weights.head()
    weights.layer(i)   -> dict of float32 arrays, see `gqa`, `kda`
    weights.experts(i, lo, hi) -> {"w_gate","w_up" [n, D, F], "w_down" [n, F, D]}
    weights.routers(), weights.router_norms()
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference_ling as rl

QUERY_BLOCK = 256


class Spec(NamedTuple):
    """The numbers of `hp` the layer functions need, hashable; the fields
    lib/reference_ling's router and experts read under its names."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    kda_heads: int
    kda_dim: int
    conv: int
    beta_scale: float
    eps: float
    router_experts: int
    top_k: int
    scale: float
    held_start: int
    held: int
    n_group: int = 1          # no groups: one of all the experts, kept
    topk_group: int = 1


def spec_of(hp: dict) -> Spec:
    lin = hp["linear_attn_config"]
    return Spec(
        hp["hidden_size"], hp["num_attention_heads"],
        hp["num_key_value_heads"], hp["head_dim"], lin["num_heads"],
        lin["head_dim"], lin["short_conv_kernel_size"],
        2.0 if hp["kda_allow_neg_eigval"] else 1.0, float(hp["rms_norm_eps"]),
        hp.get("router_num_experts", hp["n_routed_experts"]),
        hp["num_experts_per_tok"], float(hp["routed_scaling_factor"]),
        hp.get("held_experts_start", 0), hp["n_routed_experts"])


def layer_kinds(hp: dict) -> List[str]:
    """The attention kind of each layer the configuration keeps."""
    ids = hp.get("layer_ids") or list(range(hp["num_hidden_layers"]))
    assert len(ids) == hp["num_hidden_layers"]
    period = hp["gqa_interval"] + 1
    return ["gqa" if pub % period == 0 else "kda" for pub in ids]


def gqa(sp: Spec, x, w):
    """x [T, D] (normed) -> [T, D]. w: wq [D, heads*hd]; wk, wv [D,
    kv_heads*hd]; wg [D, heads*hd]; wo [heads*hd, D]."""
    T, hd, group = x.shape[0], sp.head_dim, sp.heads // sp.kv_heads
    q = (x @ w["wq"]).reshape(T, sp.heads, hd)
    k = jnp.repeat((x @ w["wk"]).reshape(T, sp.kv_heads, hd), group, axis=1)
    v = jnp.repeat((x @ w["wv"]).reshape(T, sp.kv_heads, hd), group, axis=1)
    kpos = jnp.arange(T)

    def block(args):
        qb, qpos = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    # the whole softmax row of every query, a block of queries at a time
    pad = -T % QUERY_BLOCK
    blocks = (T + pad) // QUERY_BLOCK
    o = jax.lax.map(block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            blocks, QUERY_BLOCK, sp.heads, hd),
        jnp.arange(T + pad).reshape(blocks, QUERY_BLOCK)))
    o = o.reshape(T + pad, sp.heads * hd)[:T]
    return (o * jax.nn.sigmoid(x @ w["wg"])) @ w["wo"]


def kda_inputs(sp: Spec, x, w):
    """q, k, v, g [T, H, dk] and beta [T, H] of a KDA layer."""
    T, H, dk = x.shape[0], sp.kda_heads, sp.kda_dim
    q = rl.conv_silu(x @ w["wq"], w["conv_q"]).reshape(T, H, dk)
    k = rl.conv_silu(x @ w["wk"], w["conv_k"]).reshape(T, H, dk)
    v = rl.conv_silu(x @ w["wv"], w["conv_v"]).reshape(T, H, dk)
    q, k = rl.l2_norm(q) * dk ** -0.5, rl.l2_norm(k)
    a = ((x @ w["wa_down"]) @ w["wa_up"] + w["dt_bias"]).reshape(T, H, dk)
    g = -jnp.exp(w["A_log"])[None, :, None] * jax.nn.softplus(a)
    return q, k, v, g, sp.beta_scale * jax.nn.sigmoid(x @ w["wbeta"])


def kda(sp: Spec, x, w, state_dtype=None):
    """x [T, D] (normed) -> [T, D], the recurrence one token after the other
    from a zero state."""
    T, H, dk = x.shape[0], sp.kda_heads, sp.kda_dim
    o, _ = rl.kda_recurrence(*kda_inputs(sp, x, w), state_dtype=state_dtype)
    o = rl.rms_norm(o, w["o_norm"], sp.eps)
    gate = jax.nn.sigmoid((x @ w["wg_down"]) @ w["wg_up"])
    return (o.reshape(T, H * dk) * gate) @ w["wo"]


# --- walking a model ------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jit(fn, sp: Spec, **kw):
    return jax.jit(functools.partial(fn, sp, **kw))


def hidden_states(hp: dict, weights, tokens: Sequence[int], choices=None,
                  on_router=None, activations=None, state_dtype=None):
    """The final norm's input h [T, D]. `choices` [layers, T, top_k] forces
    the experts (`reference_ling.moe`); `on_router(layer, view, experts)`
    sees each layer's router scores. The two readings that set the check's
    limits: `activations` (a dtype) rounds the residual stream and every
    normed input to it; `state_dtype` rounds every KDA state to it after
    every token."""
    sp = spec_of(hp)
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    norm = functools.partial(rl._jit_norm, eps=sp.eps)

    def rounded(x):
        return x if activations is None else x.astype(
            activations).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = rounded(weights.embed(tokens).astype(jnp.float32))
        for i, attn in enumerate(layer_kinds(hp)):
            w = weights.layer(i)
            x = rounded(norm(h, w["attn_norm"]))
            if attn == "kda":
                h = rounded(h + _jit(kda, sp, state_dtype=state_dtype)(x, w))
            else:
                h = rounded(h + _jit(gqa, sp)(x, w))
            x = rounded(norm(h, w["ffn_norm"]))
            y, experts, view = rl.moe(
                sp, x, w, functools.partial(weights.experts, i),
                None if choices is None else jnp.asarray(choices[i]))
            if on_router is not None:
                on_router(i, view, experts)
            h = rounded(h + y)
            del w
    return h


def logits_at(hp: dict, weights, tokens: Sequence[int],
              positions: Sequence[int], **kw) -> np.ndarray:
    """Float32 logits [len(positions), V] of the forward pass over `tokens`."""
    h = hidden_states(hp, weights, tokens, **kw)
    with jax.default_matmul_precision("highest"):
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        rows = rl.rms_norm(rows, weights.final_norm(), hp["rms_norm_eps"])
        return np.asarray(rows @ weights.head())


# --- the serve check ------------------------------------------------------


def teacher_forced_gaps(hp: dict, weights, prompt: Sequence[int],
                        answer: Sequence[int], routing: Optional[np.ndarray],
                        pad_multiple: int = 256, **kw) -> Dict[str, Any]:
    """Feed prompt + answer through the reference **from position 0** with
    the program's choice of experts, and measure (a) how far the returned
    token's reference logit lies under each answer position's largest and
    (b) how far each of the program's choices lies under the reference's own
    cut (`reference_ling.routing_margins`). `routing` [layers, T', top_k + 1]
    covers positions 0..T'-1 (T' = prompt + answer - 1: for a request that
    resumed from a snapshot, the shared positions' rows are those of the
    request that computed them); the reference routes the rest (the last
    token and the padding) itself. `kw`: `hidden_states`' second readings."""
    sp = spec_of(hp)
    seq = list(prompt) + list(answer)
    at = [len(prompt) - 1 + i for i in range(len(answer))]
    n = -(-len(seq) // pad_multiple) * pad_multiple
    padded = seq + [0] * (n - len(seq))
    choices, worst = None, {"expert_steps": 0.0, "same_experts": 1.0}
    if routing is not None:
        routing = np.asarray(routing)
        full = np.full((routing.shape[0], n, sp.top_k + 1), -1, np.int32)
        full[:, : routing.shape[1]] = routing
        choices = full[:, :, : sp.top_k]
    # |x| of a normed hidden state is sqrt(D) up to the norm's weight (ones
    # on seeded weights); |W_r[:, e]| is read per layer
    x_norm = np.full((n,), np.sqrt(sp.hidden), np.float32)
    w_norms = weights.router_norms()

    def on_router(m, view, _experts):
        if routing is None:
            return
        got = rl.routing_margins(sp, view, x_norm, w_norms[m], full[m])
        worst["expert_steps"] = max(worst["expert_steps"], got["expert_steps"])
        worst["same_experts"] = min(worst["same_experts"], got["same_experts"])

    lg = logits_at(hp, weights, padded, at or [len(prompt) - 1],
                   choices=choices, on_router=on_router, **kw)
    answer = np.asarray(answer, np.int64)
    top = lg.max(axis=-1)[: len(answer)]
    got = lg[np.arange(len(answer)), answer]
    return {"gaps": (top - got).tolist() or [0.0],
            "max_abs_logit": float(np.abs(lg).max()),
            "argmax_equal": int((lg.argmax(-1)[: len(answer)] == answer).sum()),
            "routing": worst}


def recurrence_inputs(replay: Dict[str, Any]) -> Dict[str, np.ndarray]:
    """What `PagedEngine.check_routing(..., mechanisms=True)` recorded of the
    slot's recurrence, in the order it ran: the rows of each prompt chunk
    from where the request resumed ("chunks": a dict a chunk of [kda_layers,
    n, ...]), then one row a decode step ([steps, kda_layers, ...]), as one
    sequence [tokens, kda_layers, ...] a name."""
    out = {}
    for name in ("q", "k", "v", "g", "beta"):
        parts = [np.moveaxis(np.asarray(c[name]), 1, 0)
                 for c in replay.get("chunks", ())]
        if name in replay:
            parts.append(np.asarray(replay[name]))
        out[name] = np.concatenate(parts, axis=0)
    return out


def mechanism_readings(replay: Dict[str, Any], routers: Sequence[Any]
                       ) -> Dict[str, float]:
    """`reference_ling.mechanism_readings` for a request whose prompt ran as
    chunks from a snapshot: the router's scores on the decode steps' inputs
    against the float32 router's, and the slot's last state against the
    token-by-token scan from the state the request resumed from ("state0":
    the snapshot's), through the rows of its chunks (`kda_chunked` in the
    program, handed from chunk to chunk and to the first decode step) and
    its decode steps. Beside each, under `..._bf16`, the second reading."""
    out = {"router_f32_steps": 0.0, "router_f32_steps_bf16": 0.0,
           "state_error": 0.0, "state_error_bf16": 0.0, "state_steps": 0,
           "chunk_rows": 0}
    if "router_x" in replay:
        for m, w in enumerate(routers):
            x, s = replay["router_x"][:, m], replay["router_s"][:, m]
            out["router_f32_steps"] = max(
                out["router_f32_steps"], rl.router_float32_steps(x, s, w))
            out["router_f32_steps_bf16"] = max(
                out["router_f32_steps_bf16"], rl.router_float32_steps(
                    x, rl.router_scores_in(x, w, jnp.bfloat16), w))
    steps = recurrence_inputs(replay)
    want = rl.replayed_state(steps, replay["state0"])
    low = rl.replayed_state(steps, replay["state0"], jnp.bfloat16)
    out["state_error"] = rl.state_error(replay["state"], want)
    out["state_error_bf16"] = rl.state_error(low, want)
    out["state_steps"] = int(steps["beta"].shape[0])
    out["chunk_rows"] = int(sum(
        np.asarray(c["beta"]).shape[1] for c in replay.get("chunks", ())))
    return out

"""The plain reference of Mellum 2: the forward pass written from the
published configuration, in straightforward float32 `jax.numpy`, one sequence
at a time from position 0, with dense causal and window masks. It shares no
code with `ray_tpu`: no kernel, no cache, no ring, no chunk, no batching. What
it shares with lib/reference_ling.py (the benchmark's own) it imports from
there: the norm, an expert block on every token, the float32 router's step.

Published layer l (pre-norm residual, `rms_norm_eps`, no bias anywhere):

    h += Attn_l(RMSNorm(h));  h += MoE(RMSNorm(h));  logits = W_head RMSNorm(h_L)

Attn  q = W_q x -> heads x hd; k, v = W_k x, W_v x -> kv_heads x hd (hd is
      `head_dim`, its own key); q <- RMSNorm_head(q), k <- RMSNorm_head(k)
      (a learned weight of hd); rotary of the layer's kind over the whole
      head in halves; softmax(q k^T / sqrt(hd) + mask) v, query head h on KV
      head h // group; out = W_o o.
Kind  `layer_types[l]`: `sliding_attention` rows i see i - sliding_window <
      j <= i; `full_attention` rows see j <= i.
Rope  sliding: inv_freq_i = theta^(-2i/hd). full (`rope_parameters
      .full_attention`, YaRN): corr(r) = hd ln(L0 / (2 pi r)) / (2 ln theta);
      low = floor(corr(beta_fast)), high = ceil(corr(beta_slow)); ramp_i =
      clip((i - low) / (high - low), 0, 1); inv_freq_i = theta^(-2i/hd) ((1 -
      ramp_i) + ramp_i / factor); cos and sin times `attention_factor`.
MoE   z = W_r x; p = softmax(z) over all the experts; the
      `num_experts_per_tok` largest; weights p_i / sum of the chosen p_j;
      y = sum w_i W2_i (silu(W1_i x) * W3_i x). No shared expert.

What the published keys do not settle is listed in the configuration file's
`assumed`. `hp` is the configuration file's dict with the program section's
`layer_ids` beside it (runners/serve_dp_mellum.reference_hp): the kept
layers' entries of the published `layer_types`. Weights arrive through a view object
(runners/_inside_mellum.ProgramWeightsMellum, or a test's own):

    weights.embed(tokens) -> [T, D];  weights.final_norm();  weights.head()
    weights.layer(i)   -> {"attn_norm", "ffn_norm", "wq", "wk", "wv", "wo",
                           "q_norm", "k_norm", "router"} float32
    weights.experts(i, lo, hi) -> {"w_gate","w_up" [n, D, F], "w_down" [n, F, D]}
    weights.routers(), weights.router_norms()

`hidden_states` takes *plants*: the same pass with one thing computed as the
configuration does not state it, which the check built on this file must
read as not correct (runners/serve_dp_mellum.py lists them).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference_ling as rl

QUERY_BLOCK = 256
WINDOW, FULL = "sliding_attention", "full_attention"
PLANTS = {
    "window_layers_full": {"windows_full": True},
    "full_layers_windowed": {"full_window": True},
    "window_minus_1": {"window_offset": -1},
    "window_plus_1": {"window_offset": +1},
    "plain_rotary_on_full": {"yarn": False},
    "attention_factor_1": {"attention_factor": 1.0},
    "kv_float8": {"kv_dtype": jnp.float8_e4m3fn},
}


class Spec(NamedTuple):
    """The numbers of `hp` the layer functions need, hashable."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    theta: float
    yarn_factor: float
    yarn_len: int
    beta_fast: float
    beta_slow: float
    attention_factor: float
    eps: float
    router_experts: int
    top_k: int


def spec_of(hp: dict) -> Spec:
    ropes = hp["rope_parameters"]
    full, slide = ropes[FULL], ropes[WINDOW]
    assert full["rope_type"] == "yarn" and slide["rope_type"] == "default"
    assert full["rope_theta"] == slide["rope_theta"]
    assert hp["norm_topk_prob"] and not hp["attention_bias"]
    return Spec(
        hp["hidden_size"], hp["num_attention_heads"],
        hp["num_key_value_heads"], hp["head_dim"], hp["sliding_window"],
        float(full["rope_theta"]), float(full["factor"]),
        int(full["original_max_position_embeddings"]),
        float(full["beta_fast"]), float(full["beta_slow"]),
        float(full["attention_factor"]), float(hp["rms_norm_eps"]),
        hp["num_experts"], hp["num_experts_per_tok"])


def layer_kinds(hp: dict) -> List[str]:
    """The attention kind of each layer the configuration keeps: the
    published `layer_types` entries of `layer_ids` (default: the first
    `num_hidden_layers`)."""
    ids = hp.get("layer_ids") or list(range(hp["num_hidden_layers"]))
    assert len(ids) == hp["num_hidden_layers"]
    assert {hp["mlp_layer_types"][i] for i in ids} == {"sparse"}
    return [hp["layer_types"][i] for i in ids]


def yarn_range(sp: Spec):
    """(low, high), truncated to whole dimensions."""
    def corr(turns):
        return sp.head_dim * math.log(sp.yarn_len / (2 * math.pi * turns)) / (
            2 * math.log(sp.theta))

    return (max(math.floor(corr(sp.beta_fast)), 0),
            min(math.ceil(corr(sp.beta_slow)), sp.head_dim - 1))


def inv_freq(sp: Spec, kind: str, yarn: bool = True) -> np.ndarray:
    half = sp.head_dim // 2
    i = np.arange(half, dtype=np.float64)
    plain = sp.theta ** (-i / half)
    if kind == WINDOW or not yarn:
        return plain.astype(np.float32)
    low, high = yarn_range(sp)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (plain * ((1.0 - ramp) + ramp / sp.yarn_factor)).astype(np.float32)


def rope(x, positions, freq, factor: float):
    """x [T, heads, hd]; rotate-half form; cos and sin times `factor`."""
    hd = x.shape[-1]
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :] * factor
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(sp: Spec, kind: str, x, w, reach=None, yarn: bool = True,
              attention_factor: Optional[float] = None, kv_dtype=None):
    """x [T, D] (normed) -> (out [T, D], o [T, heads, hd]). `reach`: the
    positions a row sees, itself included (None: all before it)."""
    T, hd, group = x.shape[0], sp.head_dim, sp.heads // sp.kv_heads
    factor = 1.0
    if kind == FULL and yarn:
        factor = (sp.attention_factor if attention_factor is None
                  else attention_factor)
    freq, pos = inv_freq(sp, kind, yarn), jnp.arange(T)
    q = rl.rms_norm((x @ w["wq"]).reshape(T, sp.heads, hd), w["q_norm"], sp.eps)
    k = rl.rms_norm((x @ w["wk"]).reshape(T, sp.kv_heads, hd), w["k_norm"],
                    sp.eps)
    v = (x @ w["wv"]).reshape(T, sp.kv_heads, hd)
    q, k = rope(q, pos, freq, factor), rope(k, pos, freq, factor)
    if kv_dtype is not None:
        # not a cast there and back: inside one program the TPU's compiler
        # drops such a pair (`xla_allow_excess_precision`)
        fmt = jnp.finfo(kv_dtype)
        k, v = (jax.lax.reduce_precision(t, fmt.nexp, fmt.nmant)
                for t in (k, v))
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)

    def block(args):
        qb, qpos = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(hd)
        seen = pos[None, None, :] <= qpos[None, :, None]
        if reach is not None:
            seen &= pos[None, None, :] > qpos[None, :, None] - reach
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    # the whole softmax row of every query, a block of queries at a time
    pad = -T % QUERY_BLOCK
    blocks = (T + pad) // QUERY_BLOCK
    o = jax.lax.map(block, (
        jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            blocks, QUERY_BLOCK, sp.heads, hd),
        jnp.arange(T + pad).reshape(blocks, QUERY_BLOCK)))
    o = o.reshape(T + pad, sp.heads, hd)[:T]
    return o.reshape(T, sp.heads * hd) @ w["wo"], o


def router_probs(x, w_router, dtype=jnp.float32):
    """softmax(W_r x) over all the experts, the matmul in `dtype`."""
    z = jnp.dot(x.astype(dtype), w_router.astype(dtype),
                preferred_element_type=dtype)
    return jax.nn.softmax(z.astype(jnp.float32), axis=-1)


def route(sp: Spec, x, w_router, dtype=jnp.float32):
    """-> (experts [T, top_k] int32, weights [T, top_k], probs [T, E])."""
    probs = router_probs(x, w_router, dtype)
    experts = jax.lax.top_k(probs, sp.top_k)[1].astype(jnp.int32)
    return experts, combine_weights(probs, experts), probs


def combine_weights(probs, experts):
    chosen = jnp.take_along_axis(probs, experts, axis=1)
    return chosen / chosen.sum(-1, keepdims=True)


@functools.lru_cache(maxsize=None)
def _jit(fn, sp: Spec, *args, **kw):
    return jax.jit(functools.partial(fn, sp, *args, **kw))


def moe(sp: Spec, x, w, experts_of, choice=None):
    """x [T, D] (normed) -> ([T, D], experts [T, top_k], router view).
    `experts_of(lo, hi)` gives the float32 weights of experts lo..hi-1;
    `choice` [T, top_k] (-1 = none given for that token) takes the place of
    the reference's own selection where given."""
    own, _, probs = _jit(route, sp)(x, w["router"])
    experts = own
    if choice is not None:
        experts = jnp.where(choice[:, :1] >= 0, choice, own)
    weights = combine_weights(probs, experts)
    y = jnp.zeros_like(x)
    for lo in range(0, sp.router_experts, rl.EXPERT_BLOCK):
        hi = min(sp.router_experts, lo + rl.EXPERT_BLOCK)
        e = experts_of(lo, hi)
        y = y + rl._jit_block(x, experts, weights, lo,
                              e["w_gate"], e["w_up"], e["w_down"])
        # one block's float32 weights alive at a time
        y.block_until_ready()
    return y, experts, {"probs": probs, "own": own}


def hidden_states(hp: dict, weights, tokens: Sequence[int], choices=None,
                  on_router=None, on_attention=None,
                  windows_full: bool = False, window_offset: int = 0,
                  full_window: bool = False,
                  yarn: bool = True, attention_factor=None, kv_dtype=None):
    """The final norm's input h [T, D]. `choices` [layers, T, top_k] forces
    the experts; `on_router(layer, view, experts)` sees each layer's router,
    `on_attention(layer, kind, o)` each layer's attention before W_o. The
    plants (`PLANTS`): `windows_full` runs the window layers full,
    `window_offset` -1 / +1 with a window one shorter or longer;
    `full_window` runs the full layers windowed; `yarn` False gives them the
    plain rotary; `attention_factor` replaces YaRN's; `kv_dtype` rounds the
    keys and values to it."""
    sp = spec_of(hp)
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    norm = functools.partial(rl._jit_norm, eps=sp.eps)
    reach = {WINDOW: None if windows_full else sp.window + window_offset,
             FULL: sp.window if full_window else None}

    with jax.default_matmul_precision("highest"):
        h = weights.embed(tokens).astype(jnp.float32)
        for i, kind in enumerate(layer_kinds(hp)):
            w = weights.layer(i)
            x = norm(h, w["attn_norm"])
            y, o = _jit(attention, sp, kind, reach=reach[kind], yarn=yarn,
                        attention_factor=attention_factor,
                        kv_dtype=kv_dtype)(x, w)
            if on_attention is not None:
                on_attention(i, kind, o)
            h = h + y
            x = norm(h, w["ffn_norm"])
            y, experts, view = moe(
                sp, x, w, functools.partial(weights.experts, i),
                None if choices is None else jnp.asarray(choices[i]))
            if on_router is not None:
                on_router(i, view, experts)
            h = h + y
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


def routing_margins(sp: Spec, view: Dict[str, Any], x_norm, w_norm,
                    program: np.ndarray) -> Dict[str, float]:
    """How far below the reference's own cut the program's selection lies,
    in steps. `program` [T, top_k + 1]: the experts the program chose (and
    its one group), -1 where it gave none. The selection is on the softmax,
    which ranks as the logits z rank: one step is 2^-8 |x| |W_r[:, e]| /
    sqrt(D), what one bf16 step on every component of the router's input,
    in a random direction, moves an expert's logit. 0 where the reference
    would have chosen the same."""
    z = np.log(np.maximum(np.asarray(view["probs"], np.float64), 1e-300))
    T, E = z.shape
    given = program[:, 0] >= 0
    experts = program[:, : sp.top_k]
    if not given.any():
        return {"expert_steps": 0.0, "same_experts": 1.0}
    step = (2.0 ** -8 * np.asarray(x_norm)[:, None] * np.asarray(w_norm)[None]
            / np.sqrt(sp.hidden))
    cut = np.sort(z, -1)[:, -sp.top_k]
    rows = np.arange(T)[:, None]
    safe = np.clip(experts, 0, E - 1)
    short = (cut[:, None] - z[rows, safe]) / step[rows, safe]
    return {"expert_steps": float(np.max(np.where(given[:, None], short, 0.0))),
            "same_experts": float(np.mean(
                np.sort(experts[given], -1)
                == np.sort(np.asarray(view["own"])[given], -1)))}


def router_float32_steps(x, probs, w_router) -> float:
    """How far the program's router probabilities [n, E] lie from the
    float32 router's on the same inputs x [n, D], in float32 steps of the
    logits: log p differs from z by a row's constant, so the row's median
    difference is taken out and one step is 2^-24 (sum_i |x_i W_ie| + |z|),
    what rounding every product to float32, all to one side, moves a logit.
    A bf16 router is about 2^15 / sqrt(D) steps away."""
    x = jnp.asarray(x).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        z = x @ w_router
        reach = jnp.abs(x) @ jnp.abs(w_router)
    got = jnp.log(jnp.maximum(jnp.asarray(probs, jnp.float32), 1e-37))
    diff = got - z
    diff = diff - jnp.median(diff, axis=-1, keepdims=True)
    step = 2.0 ** -24 * (reach + jnp.abs(z) + 1.0)
    return float(jnp.max(jnp.abs(diff) / step))


def teacher_forced_gaps(hp: dict, weights, prompt: Sequence[int],
                        answer: Sequence[int], routing: Optional[np.ndarray],
                        pad_multiple: int = 256, attn_o=None, **plants
                        ) -> Dict[str, Any]:
    """Feed prompt + answer through the reference from position 0 with the
    program's choice of experts, and measure (a) how far the returned
    token's reference logit lies under each answer position's largest, (b)
    how far each of the program's choices lies under the reference's own cut
    (`routing_margins`; `routing` [layers, T', top_k + 1] covers positions
    0..T'-1, T' = prompt + answer - 1) and (c) with `attn_o` [steps, layers,
    heads, hd], the program's attention before W_o at the decode steps
    (positions prompt .. prompt + steps - 1), how far each kind of layer's
    lies from the reference's at those positions: the worst layer's and
    position's |got - want| / |want| over the heads. `plants`:
    `hidden_states`'."""
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
    x_norm = np.full((n,), np.sqrt(sp.hidden), np.float32)
    w_norms = weights.router_norms()
    errors = {WINDOW: 0.0, FULL: 0.0}

    def on_router(m, view, _experts):
        if routing is None:
            return
        got = routing_margins(sp, view, x_norm, w_norms[m], full[m])
        worst["expert_steps"] = max(worst["expert_steps"], got["expert_steps"])
        worst["same_experts"] = min(worst["same_experts"], got["same_experts"])

    def on_attention(m, kind, o):
        if attn_o is None or not len(attn_o):
            return
        got = np.asarray(attn_o, np.float32)[:, m]          # [steps, H, hd]
        want = np.asarray(o[len(prompt): len(prompt) + got.shape[0]])
        err = (np.linalg.norm((got - want).reshape(len(got), -1), axis=1)
               / np.linalg.norm(want.reshape(len(got), -1), axis=1))
        errors[kind] = max(errors[kind], float(err.max()))

    lg = logits_at(hp, weights, padded, at or [len(prompt) - 1],
                   choices=choices, on_router=on_router,
                   on_attention=on_attention, **plants)
    answer = np.asarray(answer, np.int64)
    top = lg.max(axis=-1)[: len(answer)]
    got = lg[np.arange(len(answer)), answer]
    return {"gaps": (top - got).tolist() or [0.0],
            "max_abs_logit": float(np.abs(lg).max()),
            "argmax_equal": int((lg.argmax(-1)[: len(answer)] == answer).sum()),
            "routing": worst,
            "attn_window_error": errors[WINDOW],
            "attn_full_error": errors[FULL]}


def router_readings(replay: Dict[str, Any], routers: Sequence[Any]
                    ) -> Dict[str, float]:
    """The program's router on the decode steps' own inputs against the
    float32 router's (`router_float32_steps`), the worst layer; beside it
    the second reading, the router with weights and logits in bf16."""
    out = {"router_f32_steps": 0.0, "router_f32_steps_bf16": 0.0}
    if "router_x" not in replay:
        return out
    for m, w in enumerate(routers):
        x, s = replay["router_x"][:, m], replay["router_s"][:, m]
        out["router_f32_steps"] = max(
            out["router_f32_steps"], router_float32_steps(x, s, w))
        with jax.default_matmul_precision("highest"):
            low = router_probs(jnp.asarray(x), w, jnp.bfloat16)
        out["router_f32_steps_bf16"] = max(
            out["router_f32_steps_bf16"], router_float32_steps(x, low, w))
    return out

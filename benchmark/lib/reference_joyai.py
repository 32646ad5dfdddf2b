"""The plain reference of JoyAI-LLM-Flash (`joyai_llm_flash`): the forward pass
written from the published configuration, in straightforward float32
`jax.numpy` at `jax.default_matmul_precision("highest")`, one whole sequence
from position 0. It shares no code with `ray_tpu`: no kernel, no cache, no
chunk, no prefix, no absorbed attention, no scan over layers.

Every layer l (pre-norm residual, eps from the config, no bias anywhere):

    h += A(RMSNorm(h));  h += F(RMSNorm(h));  final RMSNorm, untied head

A(x), latent attention in its expanded form (keys and values of every head
made from the latents):
    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x [q_nope | q_rope]
    [c_kv | k_r] = x W_kva;  c = RMSNorm(c_kv);  [k_nope | v] a head = c W_kvb
    q_rope and the one k_r all heads share are rotated in adjacent pairs
    (2i, 2i + 1) by pos * theta^(-2i/rope) (`rope_interleave`); the result is
    laid out in halves, [first members | second members], on both sides of
    the dot alike (as the published modelling code does)
    scores (q_nope k_nope + q_rope k_r) / sqrt(nope + rope), causal softmax,
    o = P v, [heads x v] W_o
    what a cache keeps of a position is [c | roped k_r] (`on_latents`)
F, the first `first_k_dense_replace` layers: SwiGLU of `intermediate_size`.
F, the others: s = sigmoid(x W_r) (float32); the `num_experts_per_tok` largest
    of s + b (b the score-correction bias; one group, so no groups); weights
    s_e / sum of the chosen s x `routed_scaling_factor`; the sum over the
    chosen experts *held here*, [held_start, held_start + n_routed_experts),
    of w_e SwiGLU_e(x), plus the shared SwiGLU once. What the absent experts
    would add is left out, as in the program (model-configs guide, section 4).

`hp` is the configuration file's dict with `router_num_experts` and
`held_experts_start` beside its published keys (runners/serve_dp_joyai
.reference_hp). Weights arrive through a view object under the published
parameter names (runners/_inside_joyai.ProgramWeightsJoyAI, or a test's own),
each [in, out] float32:

    weights.embed(tokens) -> [T, D];  weights.final_norm();  weights.head()
    weights.layer(i) -> input_layernorm, post_attention_layernorm, q_a_proj,
        q_a_layernorm, q_b_proj, kv_a_proj_with_mqa, kv_a_layernorm,
        kv_b_proj, o_proj; a dense layer gate_proj, up_proj, down_proj; an
        expert layer gate (W_r), e_score_correction_bias, shared_gate_proj,
        shared_up_proj, shared_down_proj
    weights.experts(i, lo, hi) -> gate_proj, up_proj [n, D, F], down_proj
        [n, F, D] of the held experts lo..hi-1 (local numbering)

The measures that are no model's own (a router held to float32 on its own
inputs, the experts a block at a time) are lib/reference_ling.py's.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import reference_ling as rl

QUERY_BLOCK = 512
HEAD_GROUP = 8        # heads expanded and scored at a time
BANDS = 4             # runs of query blocks, each against the keys up to its end
EXPERT_BLOCK = 16     # held experts cast to float32 at a time


class Spec(NamedTuple):
    """The numbers of `hp` the layer functions need, hashable."""
    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    eps: float
    router_experts: int
    top_k: int
    scale: float
    held_start: int
    held: int


def spec_of(hp: dict) -> Spec:
    if (hp["n_group"], hp["topk_group"]) != (1, 1) or not hp["rope_interleave"]:
        raise ValueError("this reference routes over one group and rotates "
                         "in adjacent pairs")
    return Spec(
        hp["hidden_size"], hp["num_attention_heads"], hp["q_lora_rank"],
        hp["kv_lora_rank"], hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
        hp["v_head_dim"], float(hp["rope_theta"]), float(hp["rms_norm_eps"]),
        hp.get("router_num_experts", hp["n_routed_experts"]),
        hp["num_experts_per_tok"], float(hp["routed_scaling_factor"]),
        hp.get("held_experts_start", 0), hp["n_routed_experts"])


def rope_pairs(x, positions, theta, halves: bool = False):
    """x [T, heads, hd]: channel 2i turns with 2i + 1 by pos * theta^(-2i /
    hd); the result in halves. `halves` (a planted departure): channel i
    turns with i + hd/2 instead."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    if halves:
        a, b = x[..., : hd // 2], x[..., hd // 2:]
    else:
        a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], -1)


def mla(sp: Spec, x, w, positions, plant: Optional[str] = None):
    """x [T, D] (normed; T a multiple of QUERY_BLOCK or below it) -> ([T, D],
    latents [T, rank + rope] = [c | roped k_r]). `plant`: "no_q_norm" leaves
    the query's norm out, "rope_halves" rotates in halves."""
    T, H = x.shape[0], sp.heads
    c_q = x @ w["q_a_proj"]
    if plant != "no_q_norm":
        c_q = rl.rms_norm(c_q, w["q_a_layernorm"], sp.eps)
    halves = plant == "rope_halves"
    ckr = x @ w["kv_a_proj_with_mqa"]
    c = rl.rms_norm(ckr[:, : sp.kv_rank], w["kv_a_layernorm"], sp.eps)
    k_r = rope_pairs(ckr[:, None, sp.kv_rank:], positions, sp.theta,
                     halves)[:, 0]
    wq = w["q_b_proj"].reshape(sp.q_rank, H, sp.nope + sp.rope)
    wkv = w["kv_b_proj"].reshape(sp.kv_rank, H, sp.nope + sp.v_dim)
    qb, hg = min(QUERY_BLOCK, T), min(HEAD_GROUP, H)
    n_blocks = T // qb
    bands = min(BANDS, n_blocks)

    def heads(weights):
        """`hg` heads at a time: their queries, keys and values made from
        the latents, every query block against the keys before its end."""
        wq_g, wkv_g = weights
        q = jnp.einsum("tr,rhd->thd", c_q, wq_g)
        q_nope = q[..., : sp.nope]
        q_r = rope_pairs(q[..., sp.nope:], positions, sp.theta, halves)
        kv = jnp.einsum("tc,chd->thd", c, wkv_g)
        k_nope, v = kv[..., : sp.nope], kv[..., sp.nope:]

        def block(args, seen):
            """A block of queries against the keys of the first `seen`
            positions: what lies behind a row's own is masked."""
            qn, qr, qpos = args
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope[:seen])
                 + jnp.einsum("qhd,kd->hqk", qr, k_r[:seen])
                 ) / np.sqrt(sp.nope + sp.rope)
            s = jnp.where(jnp.arange(seen)[None, None, :]
                          <= qpos[None, :, None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1),
                              v[:seen])

        # the query blocks in `BANDS` runs, each run against the keys up to
        # its own end: no key behind every row of a run is scored, and a
        # run's blocks are one `lax.map` (one body to compile a run)
        rows = []
        for band in range(bands):
            lo = band * n_blocks // bands * qb
            hi = (band + 1) * n_blocks // bands * qb
            o = jax.lax.map(functools.partial(block, seen=hi), (
                q_nope[lo:hi].reshape(-1, qb, hg, sp.nope),
                q_r[lo:hi].reshape(-1, qb, hg, sp.rope),
                jnp.arange(lo, hi).reshape(-1, qb)))
            rows.append(o.reshape(hi - lo, hg, sp.v_dim))
        return jnp.concatenate(rows, 0)

    o = jax.lax.map(heads, (
        wq.reshape(sp.q_rank, H // hg, hg, -1).transpose(1, 0, 2, 3),
        wkv.reshape(sp.kv_rank, H // hg, hg, -1).transpose(1, 0, 2, 3)))
    o = o.transpose(1, 0, 2, 3).reshape(T, H * sp.v_dim)
    return o @ w["o_proj"], jnp.concatenate([c, k_r], -1)


def route(sp: Spec, x, w_router, bias):
    """-> (experts [T, top_k] int32, s [T, E], s + b [T, E])."""
    s = jax.nn.sigmoid(x @ w_router)
    sb = s + bias
    return jax.lax.top_k(sb, sp.top_k)[1].astype(jnp.int32), s, sb


combine_weights = rl.combine_weights      # reads `sp.scale` alone


@functools.lru_cache(maxsize=None)
def _jit(fn, sp: Spec, **static):
    return jax.jit(functools.partial(fn, sp, **static))


def moe(sp: Spec, x, w, experts_of, choice=None):
    """x [T, D] (normed) -> ([T, D], router view). `experts_of(lo, hi)` gives
    the float32 weights of held experts lo..hi-1; `choice` [T, top_k] (-1 =
    none given for that token) takes the place of the reference's own
    selection where given."""
    own, s, sb = _jit(route, sp)(x, w["gate"], w["e_score_correction_bias"])
    experts = own
    if choice is not None:
        experts = jnp.where(choice[:, :1] >= 0, choice, own)
    weights = combine_weights(sp, s, experts)
    y = jnp.zeros_like(x)
    for lo in range(0, sp.held, EXPERT_BLOCK):
        hi = min(sp.held, lo + EXPERT_BLOCK)
        e = experts_of(lo, hi)
        y = y + rl._jit_block(x, experts, weights, sp.held_start + lo,
                              e["gate_proj"], e["up_proj"], e["down_proj"])
        # one block's float32 weights alive at a time
        y.block_until_ready()
    y = y + rl._jit_swiglu(x, w["shared_gate_proj"], w["shared_up_proj"],
                           w["shared_down_proj"])
    return y, {"s": s, "sb": sb, "own": own}


def hidden_states(hp: dict, weights, tokens: Sequence[int], choices=None,
                  on_router=None, on_latents=None, activations=None,
                  plant: Optional[str] = None):
    """The final norm's input h [T, D]. `choices` [n_moe, T, top_k] forces the
    experts (see `moe`); `on_router(moe_index, view)` sees each expert layer's
    router scores; `on_latents(layer, latents [T, rank + rope])` what a cache
    would keep of each layer. `activations` (a dtype) rounds the residual
    stream and every normed input to it: what a program in that precision
    would at least lose (the logit limit's second reading). `plant`: see
    `mla`."""
    sp = spec_of(hp)
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    norm = functools.partial(rl._jit_norm, eps=sp.eps)

    def rounded(x):
        return x if activations is None else x.astype(
            activations).astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        h = rounded(weights.embed(tokens).astype(jnp.float32))
        for i in range(hp["num_hidden_layers"]):
            w = weights.layer(i)
            x = rounded(norm(h, w["input_layernorm"]))
            y, lat = _jit(mla, sp, plant=plant)(x, w, positions)
            if on_latents is not None:
                on_latents(i, lat)
            h = rounded(h + y)
            del y, lat
            x = rounded(norm(h, w["post_attention_layernorm"]))
            m = i - hp["first_k_dense_replace"]
            if m < 0:
                h = h + rl._jit_swiglu(x, w["gate_proj"], w["up_proj"],
                                       w["down_proj"])
            else:
                y, view = moe(sp, x, w, functools.partial(weights.experts, i),
                              None if choices is None
                              else jnp.asarray(choices[m]))
                if on_router is not None:
                    on_router(m, view)
                h = h + y
            h = rounded(h)
            del w
    return h


def logits_at(hp: dict, weights, tokens: Sequence[int],
              positions: Sequence[int], **kw) -> np.ndarray:
    """Float32 logits [len(positions), V] of the forward pass over `tokens`;
    keywords as `hidden_states`."""
    h = hidden_states(hp, weights, tokens, **kw)
    with jax.default_matmul_precision("highest"):
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        rows = rl.rms_norm(rows, weights.final_norm(), hp["rms_norm_eps"])
        return np.asarray(rows @ weights.head())


# --- the serve check ------------------------------------------------------


def routing_margins(sp: Spec, view: Dict[str, Any], w_norm,
                    program: np.ndarray) -> Dict[str, float]:
    """How far below the reference's own cut the program's selection lies, in
    steps. `program` [T, top_k + 1]: the experts the program chose (and its
    one group's mask), -1 where it gave none. One step is 2^-8 |x| |W_r[:, e]|
    / sqrt(D) / 4 with |x| = sqrt(D): what one bf16 step on every component
    of the router's normed input, in a random direction, moves an expert's
    sigmoid score (slope <= 1/4). expert_steps: the reference's `top_k`-th
    best s + b less the program's chosen expert's s + b, the worst pair; 0
    where the reference would have chosen the same."""
    sb = np.asarray(view["sb"])
    T, E = sb.shape
    given = program[:, 0] >= 0
    if not given.any():
        return {"expert_steps": 0.0, "same_experts": 1.0}
    experts = np.clip(program[:, : sp.top_k], 0, E - 1)
    step = 2.0 ** -8 * np.asarray(w_norm)[None] / 4.0                # [1, E]
    cut = np.sort(sb, -1)[:, -sp.top_k]
    rows = np.arange(T)[:, None]
    short = np.where(given[:, None],
                     (cut[:, None] - sb[rows, experts]) / step[0][experts], 0.0)
    return {"expert_steps": float(np.max(short)),
            "at_position": int(np.argmax(short.max(-1))),
            "same_experts": float(np.mean(
                np.sort(experts[given], -1)
                == np.sort(np.asarray(view["own"])[given], -1)))}


def latent_errors(got, want) -> Dict[str, float]:
    """A layer's cached latents got [T, rank + rope] against the reference's:
    `cache_error` |got - want| / |want| over the whole layer (Frobenius), and
    `cache_row_error`, the worst single position's."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    d2, w2 = ((got - want) ** 2).sum(-1), (want ** 2).sum(-1)
    return {"cache_error": float(np.sqrt(d2.sum() / w2.sum())),
            "cache_row_error": float(np.sqrt((d2 / w2).max()))}


def in_float8(x) -> np.ndarray:
    """x rounded to float8_e4m3fn: the precision below a bf16 cache."""
    return np.asarray(jnp.asarray(x, jnp.float32).astype(
        jnp.float8_e4m3fn).astype(jnp.float32))


def conversation_gaps(hp: dict, weights, tokens: Sequence[int],
                      judged: Sequence[Sequence[int]],
                      routing: Optional[np.ndarray], cached,
                      pad_multiple: int = 512,
                      second_readings: bool = False,
                      plant: Optional[str] = None) -> Dict[str, Any]:
    """Feed one conversation's `tokens` (its last turn's prompt and answer)
    through the reference once, from position 0, with the program's choice of
    experts, and measure:

    (a) `gaps`: for each (position, token) of `judged` (a served token and
        the position whose logits chose it: every turn's first token, the
        last turn's all), how far the token's reference logit lies under the
        position's largest, as lib/reference.py does;
    (b) `routing`: `routing_margins`, the worst expert layer's; `routing`
        [n_moe, T', top_k + 1] covers the positions the program computed, the
        reference routes the rest (the last token and the padding) itself;
    (c) `cache_error`, `cache_row_error`: `cached(layer)` -> the program's
        cached latents of the conversation's first positions [n, rank + rope],
        against the reference's `[c | roped k_r]` there, the worst layer's
        (`latent_errors`); `cache_error_first`, the first layer's alone,
        where no layer's activations lie before the cache's own precision.

    `second_readings`: also the program's latents rounded to float8 judged
    the same way (`cache_error_float8`, `cache_error_first_float8`), and the
    gaps and routing margins of the reference itself with float8 activations
    (`gaps_float8`, `expert_steps_float8`): what the limits must refuse."""
    sp = spec_of(hp)
    seq = list(tokens)
    n = -(-len(seq) // pad_multiple) * pad_multiple
    padded = seq + [0] * (n - len(seq))
    choices, full = None, None
    if routing is not None:
        routing = np.asarray(routing)
        full = np.full((routing.shape[0], n, sp.top_k + 1), -1, np.int32)
        full[:, : routing.shape[1]] = routing
        choices = full[:, :, : sp.top_k]
    w_norms = weights.router_norms()
    cache = {"cache_error": 0.0, "cache_row_error": 0.0,
             "cache_error_first": 0.0, "cache_error_float8": 0.0,
             "cache_error_first_float8": 0.0, "cache_worst_layer": -1,
             "cache_error_by_layer": []}

    def margins():
        """(the worst margins so far, each layer's with its position, the
        `on_router` that fills them): one set a pass of the reference."""
        worst = {"expert_steps": 0.0, "same_experts": 1.0}
        by_layer = []

        def on_router(m, view):
            if full is None:
                return
            got = routing_margins(sp, view, w_norms[m], full[m])
            by_layer.append((round(got["expert_steps"], 1), got["at_position"]))
            worst["expert_steps"] = max(worst["expert_steps"],
                                        got["expert_steps"])
            worst["same_experts"] = min(worst["same_experts"],
                                        got["same_experts"])

        return worst, by_layer, on_router

    def on_latents(layer, lat):
        got = np.asarray(cached(layer), np.float32)
        want = np.asarray(lat[: got.shape[0]])
        e = latent_errors(got, want)
        cache["cache_error_by_layer"].append(round(e["cache_error"], 5))
        if e["cache_error"] > cache["cache_error"]:
            cache["cache_worst_layer"] = layer
        for k, v in e.items():
            cache[k] = max(cache[k], v)
        if layer == 0:
            cache["cache_error_first"] = e["cache_error"]
        if second_readings:
            low = latent_errors(in_float8(got), want)["cache_error"]
            cache["cache_error_float8"] = max(cache["cache_error_float8"], low)
            if layer == 0:
                cache["cache_error_first_float8"] = low

    at = [p for p, _ in judged]
    answer = np.asarray([t for _, t in judged], np.int64)

    def gaps_of(lg):
        return (lg.max(axis=-1) - lg[np.arange(len(answer)), answer]).tolist()

    worst, by_layer, on_router = margins()
    lg = logits_at(hp, weights, padded, at, choices=choices,
                   on_router=on_router,
                   on_latents=on_latents if cached is not None else None,
                   plant=plant)
    out = {"gaps": gaps_of(lg), "max_abs_logit": float(np.abs(lg).max()),
           "argmax_equal": int((lg.argmax(-1) == answer).sum()),
           "routing": worst, "expert_steps_by_layer": by_layer, **cache}
    if second_readings:
        low_worst, _, on_router = margins()
        low = logits_at(hp, weights, padded, at, choices=choices,
                        on_router=on_router, activations=jnp.float8_e4m3fn)
        out["gaps_float8"] = gaps_of(low)
        out["expert_steps_float8"] = low_worst["expert_steps"]
    return out

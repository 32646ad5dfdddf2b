"""The plain reference of the Ling-3.0-flash language model: the forward pass
written from the published configuration, in straightforward float32
`jax.numpy`, one sequence at a time. It shares no code with `ray_tpu`: no
kernel, no cache, no batching, no chunked scan, no absorbed attention.

Published layer l (pre-norm residual, eps from the config):

    h += Attn_l(RMSNorm(h));  h += FFN_l(RMSNorm(h))

`Attn_l` is latent attention (MLA) where (l + 1) % layer_group_size == 0 and
delta-rule linear attention with a decay per channel (KDA) elsewhere; `FFN_l`
is a dense SwiGLU for the leading layers and shared + routed experts after
them. A configuration that keeps some of the published layers names them in
`layer_ids` (their published indices decide the kind of attention) and counts
its leading dense layers in `first_k_dense_replace`.

KDA (H heads, d_k = d_v = head_dim), the token-by-token recurrence:
    q, k, v = conv4_silu(W_q x), conv4_silu(W_k x), conv4_silu(W_v x)
    q, k L2-normalised per head, q scaled by d_k^-1/2
    g_t = kda_lower_bound * sigmoid(exp(A_log_h) (W_a x + dt_bias))    (< 0)
    beta_t = sigmoid(W_beta x)
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t;  out = W_o (sigmoid(W_g x)_h * RMSNorm_head(o_t))
MLA, no q_lora, expanded:
    q = W_q x -> H x (nope + rope);  [c; k_r] = W_kva x;  c = RMSNorm(c)
    [k_nope; v] = W_kvb c;  rope on q_r and the one shared k_r
    scores (q_nope k_nope + q_r k_r) / sqrt(nope + rope), causal softmax,
    head-wise gate as above, W_o
MoE: s = sigmoid(W_r x) in float32; selection on s + b; groups scored by the
sum of their two largest s + b, `topk_group` groups kept, `num_experts_per_tok`
experts chosen among them; weights s_i / sum s_j * routed_scaling_factor;
y = sum_{chosen and held} w_i E_i(x) + E_shared(x). Only the experts
[held_start, held_start + num_experts) are held: what the others would add is
left out, as in the program (model-configs guide, section 4).

Departures and inferences are listed in the configuration file's `assumed`.

`hp` is the configuration file's dict. Weights arrive through a view object
(runners/_inside_ling.ProgramWeightsLing, or a test's own):

    weights.embed(tokens) -> [T, D];  weights.final_norm();  weights.head()
    weights.layer(i)   -> dict of float32 arrays, see `kda`, `mla`, `ffn`
    weights.experts(i, lo, hi) -> {"w_gate","w_up" [n, D, F], "w_down" [n, F, D]}
                          of the held experts lo..hi-1 (local numbering)

Rotary embedding in the rotate-half form (the published `rope_interleave`
permutes the rope channels of q and k alike, which no score can see).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512
EXPERT_BLOCK = 16     # held experts cast to float32 at a time (0.38 GB)


class Spec(NamedTuple):
    """The numbers of `hp` the layer functions need, hashable."""
    hidden: int
    heads: int
    head_dim: int
    conv: int
    gate_low: float
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    theta: float
    eps: float
    router_experts: int
    n_group: int
    topk_group: int
    top_k: int
    scale: float
    held_start: int
    held: int


def spec_of(hp: dict) -> Spec:
    return Spec(
        hp["hidden_size"], hp["num_attention_heads"], hp["head_dim"],
        hp["short_conv_kernel_size"], float(hp["kda_lower_bound"]),
        hp["kv_lora_rank"], hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
        hp["v_head_dim"], float(hp["rope_theta"]), float(hp["rms_norm_eps"]),
        hp.get("router_num_experts", hp["num_experts"]), hp["n_group"],
        hp["topk_group"], hp["num_experts_per_tok"],
        float(hp["routed_scaling_factor"]), hp.get("held_experts_start", 0),
        hp["num_experts"])


def layer_kinds(hp: dict) -> List[Tuple[str, str]]:
    """[(attention kind, ffn kind)] of the layers the configuration keeps."""
    ids = hp.get("layer_ids") or list(range(hp["num_hidden_layers"]))
    assert len(ids) == hp["num_hidden_layers"]
    return [("mla" if (pub + 1) % hp["layer_group_size"] == 0 else "kda",
             "dense" if i < hp["first_k_dense_replace"] else "moe")
            for i, pub in enumerate(ids)]


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def rope(x, positions, theta):
    """x [T, heads, hd]; rotate-half form, frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def conv_silu(x, w):
    """Causal depthwise convolution then SiLU. x [T, C]; w [K, C], w[K-1]
    on the current position; nothing before position 0."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return jax.nn.silu(sum(xp[j:j + T] * w[j] for j in range(K)))


def kda_recurrence(q, k, v, g, beta, state=None, state_dtype=None):
    """The delta rule, one token after the other. q, k, g [T, H, dk];
    v [T, H, dv]; beta [T, H]. Returns (o [T, H, dv], last state).
    `state_dtype` rounds the state to that type after every token: what a
    program that kept it there would carry (the state check's second
    reading)."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    if state is None:
        state = jnp.zeros((H, dk, dv), jnp.float32)

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = jnp.exp(gt)[:, :, None] * S
        S = S + bt[:, None, None] * kt[:, :, None] * (
            vt - jnp.einsum("hk,hkv->hv", kt, S))[:, None, :]
        if state_dtype is not None:
            # not a pair of casts: under jit a TPU's compiler may keep the
            # excess precision and drop them
            kind = jnp.finfo(state_dtype)
            S = jax.lax.reduce_precision(S, kind.nexp, kind.nmant)
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    state, o = jax.lax.scan(step, state, (q, k, v, g, beta))
    return o, state


def kda_gates(sp: Spec, x, w):
    """g [T, H, dk] (log decay, in (gate_low, 0)) and beta [T, H]."""
    T, H, dk = x.shape[0], sp.heads, sp.head_dim
    a = (x @ w["wa"]).reshape(T, H, dk) + w["dt_bias"].reshape(H, dk)
    g = sp.gate_low * jax.nn.sigmoid(jnp.exp(w["A_log"])[None, :, None] * a)
    return g, jax.nn.sigmoid(x @ w["wbeta"])


def kda(sp: Spec, x, w):
    """x [T, D] (normed) -> [T, D]. w: wq, wk, wv, wa [D, H*dk]; conv_q,
    conv_k, conv_v [K, H*dk]; wbeta, wg [D, H]; A_log [H]; dt_bias [H*dk];
    o_norm [dv]; wo [H*dv, D]."""
    T, H, dk = x.shape[0], sp.heads, sp.head_dim
    q = conv_silu(x @ w["wq"], w["conv_q"]).reshape(T, H, dk)
    k = conv_silu(x @ w["wk"], w["conv_k"]).reshape(T, H, dk)
    v = conv_silu(x @ w["wv"], w["conv_v"]).reshape(T, H, dk)
    q, k = l2_norm(q) * dk ** -0.5, l2_norm(k)
    g, beta = kda_gates(sp, x, w)
    o, _ = kda_recurrence(q, k, v, g, beta)
    o = rms_norm(o, w["o_norm"], sp.eps)
    o = o * jax.nn.sigmoid(x @ w["wg"])[:, :, None]
    return o.reshape(T, H * dk) @ w["wo"]


def mla(sp: Spec, x, w, positions):
    """x [T, D] (normed) -> [T, D]. w: wq [D, H*(nope+rope)]; wkva [D,
    rank+rope]; kv_norm [rank]; wkvb [rank, H*(nope+v)]; wg [D, H]; wo
    [H*v, D]."""
    T, H = x.shape[0], sp.heads
    q = (x @ w["wq"]).reshape(T, H, sp.nope + sp.rope)
    q_nope, q_r = q[..., : sp.nope], rope(q[..., sp.nope:], positions, sp.theta)
    ckr = x @ w["wkva"]
    c = rms_norm(ckr[:, : sp.kv_rank], w["kv_norm"], sp.eps)
    k_r = rope(ckr[:, None, sp.kv_rank:], positions, sp.theta)   # [T, 1, rope]
    kv = (c @ w["wkvb"]).reshape(T, H, sp.nope + sp.v_dim)
    k_nope, v = kv[..., : sp.nope], kv[..., sp.nope:]
    kpos = jnp.arange(T)
    out = []
    for start in range(0, T, QUERY_BLOCK):
        sl = slice(start, start + QUERY_BLOCK)
        qpos = start + jnp.arange(q_nope[sl].shape[0])
        s = (jnp.einsum("qhd,khd->hqk", q_nope[sl], k_nope)
             + jnp.einsum("qhd,kd->hqk", q_r[sl], k_r[:, 0])
             ) / np.sqrt(sp.nope + sp.rope)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v))
    o = jnp.concatenate(out, 0) * jax.nn.sigmoid(x @ w["wg"])[:, :, None]
    return o.reshape(T, H * sp.v_dim) @ w["wo"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def router_scores(sp: Spec, x, w_router, bias):
    """s [T, E] and the scores the selection sees, s + b."""
    s = jax.nn.sigmoid(x @ w_router)
    return s, s + bias


def group_scores(sp: Spec, choice_scores):
    """[T, n_group]: the sum of each group's two largest s + b."""
    T = choice_scores.shape[0]
    per = choice_scores.reshape(T, sp.n_group, -1)
    return jax.lax.top_k(per, 2)[0].sum(-1)


def route(sp: Spec, x, w_router, bias):
    """-> (experts [T, top_k] int32, weights [T, top_k], kept groups [T,
    n_group] bool)."""
    T = x.shape[0]
    s, sb = router_scores(sp, x, w_router, bias)
    gs = group_scores(sp, sb)
    kept_idx = jax.lax.top_k(gs, sp.topk_group)[1]
    kept = jnp.zeros((T, sp.n_group), bool).at[
        jnp.arange(T)[:, None], kept_idx].set(True)
    admissible = jnp.repeat(kept, sp.router_experts // sp.n_group, axis=1)
    experts = jax.lax.top_k(jnp.where(admissible, sb, -jnp.inf), sp.top_k)[1]
    return experts.astype(jnp.int32), combine_weights(sp, s, experts), kept


def combine_weights(sp: Spec, s, experts):
    chosen = jnp.take_along_axis(s, experts, axis=1)
    return chosen / chosen.sum(-1, keepdims=True) * sp.scale


def routed_block(x, experts, weights, first, w_gate, w_up, w_down):
    """What the held experts first..first+n-1 (global numbers) add: every
    expert of the block on every token, kept where the token chose it."""
    y = jnp.zeros_like(x)
    for j in range(w_gate.shape[0]):
        wt = jnp.sum(jnp.where(experts == first + j, weights, 0.0), axis=1)
        y = y + wt[:, None] * swiglu(x, w_gate[j], w_up[j], w_down[j])
    return y


# --- walking a model ------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jit(fn, sp: Spec):
    return jax.jit(functools.partial(fn, sp))


_jit_block = jax.jit(routed_block, static_argnums=(3,))
_jit_swiglu = jax.jit(swiglu)
_jit_norm = jax.jit(rms_norm)


def moe(sp: Spec, x, w, experts_of, choice=None, shared: bool = True):
    """x [T, D] (normed) -> ([T, D], experts [T, top_k], router view).
    `experts_of(lo, hi)` gives the float32 weights of held experts lo..hi-1;
    `choice` [T, top_k] (-1 = none given for that token) takes the place of
    the reference's own selection where given."""
    own, _, kept = _jit(route, sp)(x, w["router"], w["router_bias"])
    s, sb = _jit(router_scores, sp)(x, w["router"], w["router_bias"])
    experts = own
    if choice is not None:
        given = (choice[:, :1] >= 0)
        experts = jnp.where(given, choice, own)
    weights = combine_weights(sp, s, experts)
    y = jnp.zeros_like(x)
    for lo in range(0, sp.held, EXPERT_BLOCK):
        hi = min(sp.held, lo + EXPERT_BLOCK)
        e = experts_of(lo, hi)
        y = y + _jit_block(x, experts, weights, sp.held_start + lo,
                           e["w_gate"], e["w_up"], e["w_down"])
        # one block's float32 weights alive at a time: dispatch runs ahead
        # of the device and would allocate every block's at once
        y.block_until_ready()
    if shared:
        y = y + _jit_swiglu(x, w["sh_gate"], w["sh_up"], w["sh_down"])
    return y, experts, {"s": s, "sb": sb, "kept": kept, "own": own}


def hidden_states(hp: dict, weights, tokens: Sequence[int], choices=None,
                  on_router=None, activations=None):
    """The final norm's input h [T, D]. `choices` [n_moe, T, top_k] forces
    the experts (see `moe`); `on_router(moe_index, view, experts)` sees each
    expert layer's router scores. `activations` (a dtype) rounds the residual
    stream and every normed input to it: what a program in that precision
    would at least lose, for the reading that sets the check's limits."""
    sp = spec_of(hp)
    tokens = jnp.asarray(np.asarray(tokens, np.int32))
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    norm = functools.partial(_jit_norm, eps=sp.eps)

    def rounded(x):
        return x if activations is None else x.astype(
            activations).astype(jnp.float32)

    m = 0
    with jax.default_matmul_precision("highest"):
        h = rounded(weights.embed(tokens).astype(jnp.float32))
        for i, (attn, ffn) in enumerate(layer_kinds(hp)):
            w = weights.layer(i)
            x = rounded(norm(h, w["attn_norm"]))
            if attn == "kda":
                h = rounded(h + _jit(kda, sp)(x, w))
            else:
                h = rounded(h + _jit(mla, sp)(x, w, positions))
            x = rounded(norm(h, w["ffn_norm"]))
            if ffn == "dense":
                h = h + _jit_swiglu(x, w["w_gate"], w["w_up"], w["w_down"])
            else:
                y, experts, view = moe(
                    sp, x, w, functools.partial(weights.experts, i),
                    None if choices is None else jnp.asarray(choices[m]))
                if on_router is not None:
                    on_router(m, view, experts)
                h = h + y
                m += 1
            h = rounded(h)
            del w
    return h


def logits_at(hp: dict, weights, tokens: Sequence[int],
              positions: Sequence[int], choices=None, on_router=None,
              activations=None) -> np.ndarray:
    """Float32 logits [len(positions), V] of the forward pass over `tokens`."""
    h = hidden_states(hp, weights, tokens, choices, on_router, activations)
    with jax.default_matmul_precision("highest"):
        rows = h[jnp.asarray(np.asarray(positions, np.int32))]
        rows = rms_norm(rows, weights.final_norm(), hp["rms_norm_eps"])
        return np.asarray(rows @ weights.head())


# --- the serve check ------------------------------------------------------


def routing_margins(sp: Spec, view: Dict[str, Any], x_norm, w_norm,
                    program: np.ndarray) -> Dict[str, float]:
    """How far below the reference's own cut the program's selection lies,
    in steps. `program` [T, top_k + 1]: the experts the program chose and a
    bit mask of the groups it kept, -1 where it gave none. One step is
    2^-8 |x| |W_r[:, e]| / sqrt(D) / 4: what one bf16 step on every
    component of the router's input, in a random direction, moves an
    expert's sigmoid score (slope <= 1/4); a group's step is twice its
    largest expert's (its score is a sum of two).

    expert: the reference's `top_k`-th best s + b among the experts of the
            groups the program kept, less the program's chosen expert's s + b
    group:  the reference's `topk_group`-th best group score, less the
            kept group's score
    Both 0 where the reference would have chosen the same."""
    sb = np.asarray(view["sb"])
    T, E = sb.shape
    per_group = E // sp.n_group
    given = program[:, 0] >= 0
    experts, mask = program[:, : sp.top_k], program[:, sp.top_k]
    step_e = (2.0 ** -8 * np.asarray(x_norm)[:, None] * np.asarray(w_norm)[None]
              / np.sqrt(sp.hidden) / 4.0)                       # [T, E]
    kept = (mask[:, None] >> np.arange(sp.n_group)[None]) & 1    # [T, G]
    gs = np.sort(sb.reshape(T, sp.n_group, per_group), -1)[..., -2:].sum(-1)
    cut_g = np.sort(gs, -1)[:, -sp.topk_group]
    step_g = 2.0 * step_e.reshape(T, sp.n_group, per_group).max(-1)
    short_g = np.where(kept.astype(bool),
                       (cut_g[:, None] - gs) / step_g, 0.0)
    admissible = np.repeat(kept.astype(bool), per_group, axis=1)
    cut_e = np.sort(np.where(admissible, sb, -np.inf), -1)[:, -sp.top_k]
    rows = np.arange(T)[:, None]
    safe = np.clip(experts, 0, E - 1)
    short_e = (cut_e[:, None] - sb[rows, safe]) / step_e[rows, safe]
    # an expert outside the kept groups is no selection at all
    short_e = np.where(admissible[rows, safe], short_e, np.inf)
    if not given.any():
        return {"expert_steps": 0.0, "group_steps": 0.0, "same_experts": 1.0}
    g = given[:, None]
    return {"expert_steps": float(np.max(np.where(g, short_e, 0.0))),
            "group_steps": float(np.max(np.where(g, short_g, 0.0))),
            "same_experts": float(np.mean(
                np.sort(experts[given], -1)
                == np.sort(np.asarray(view["own"])[given], -1)))}


def router_scores_in(x, w_router, dtype):
    """The router computed in `dtype` on inputs x [n, D]: weights and logits
    rounded to it (float32: the reference's own router)."""
    with jax.default_matmul_precision("highest"):
        z = jnp.dot(jnp.asarray(x).astype(dtype), w_router.astype(dtype),
                    preferred_element_type=dtype)
    return jax.nn.sigmoid(z.astype(jnp.float32))


def router_float32_steps(x, s, w_router) -> float:
    """How far router scores s [n, E] lie from the float32 router's on the
    same inputs x [n, D] (what the program's router was given, so the
    activations' precision plays no part), in float32 steps, the worst of
    n * E. One step is what rounding every product x_i W_ie to float32
    (2^-24 of its size), all to one side, and the score once, moves a score:
    2^-24 (s (1 - s) sum_i |x_i W_ie| + s). A float32 matmul's error grows
    with the square root of D where this bound grows with D, so it reads far
    under one step in any order of summation; weights or logits in bf16 are
    about 2^15 / sqrt(D) steps away."""
    x = jnp.asarray(x).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        reach = jnp.abs(x) @ jnp.abs(w_router)
    s_ref = router_scores_in(x, w_router, jnp.float32)
    step = 2.0 ** -24 * (s_ref * (1.0 - s_ref) * reach + s_ref)
    return float(jnp.max(jnp.abs(jnp.asarray(s) - s_ref) / step))


def replayed_state(steps: Dict[str, Any], state0, state_dtype=None):
    """The recurrent state [kda_layers, H, dk, dv] that `kda_recurrence`
    arrives at from `state0` through the inputs the program's decode steps
    recorded: steps["q"], ["k"], ["v"], ["g"] [n, kda_layers, H, dk] and
    ["beta"] [n, kda_layers, H]."""
    def one(q, k, v, g, beta, s0):
        return kda_recurrence(q, k, v, g, beta, s0, state_dtype)[1]

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.vmap(one, in_axes=(1, 1, 1, 1, 1, 0)))(
            *(jnp.asarray(steps[n], jnp.float32)
              for n in ("q", "k", "v", "g", "beta")),
            jnp.asarray(state0, jnp.float32))


def state_error(state, want) -> float:
    """The worst head's |state - want| / |want| (Frobenius norms over a
    head's [dk, dv]), over every layer's heads."""
    state, want = np.asarray(state, np.float64), np.asarray(want, np.float64)
    diff = np.sqrt(((state - want) ** 2).sum((-2, -1)))
    return float(np.max(diff / np.sqrt((want ** 2).sum((-2, -1)))))


def mechanism_readings(replay: Dict[str, Any], routers: Sequence[Any]
                       ) -> Dict[str, float]:
    """What `PagedEngine.check_routing(..., mechanisms=True)` recorded of a
    request's decode steps, held to the reference on the same inputs:
    `router_f32_steps` (see `router_float32_steps`, the worst expert layer's)
    and `state_error` (`state_error` of the program's last state against
    `replayed_state`). Beside each, under `..._bf16`, the second reading:
    the reference itself with a bf16 router, or a state rounded to bf16 after
    every token, judged the same way. `routers`: W_r of every expert layer,
    float32."""
    out = {"router_f32_steps": 0.0, "router_f32_steps_bf16": 0.0,
           "state_error": 0.0, "state_error_bf16": 0.0, "state_steps": 0}
    if "beta" not in replay:       # the answer ended before a decode step
        return out
    for m, w in enumerate(routers):
        x, s = replay["router_x"][:, m], replay["router_s"][:, m]
        out["router_f32_steps"] = max(
            out["router_f32_steps"], router_float32_steps(x, s, w))
        out["router_f32_steps_bf16"] = max(
            out["router_f32_steps_bf16"], router_float32_steps(
                x, router_scores_in(x, w, jnp.bfloat16), w))
    want = replayed_state(replay, replay["state0"])
    low = replayed_state(replay, replay["state0"], jnp.bfloat16)
    out["state_error"] = state_error(replay["state"], want)
    out["state_error_bf16"] = state_error(low, want)
    out["state_steps"] = int(np.asarray(replay["beta"]).shape[0])
    return out


def teacher_forced_gaps(hp: dict, weights, prompt: Sequence[int],
                        answer: Sequence[int], routing: Optional[np.ndarray],
                        pad_multiple: int = 256) -> Dict[str, Any]:
    """Feed prompt + answer through the reference with the program's choice
    of experts, and measure (a) how far the returned token's reference logit
    lies under each answer position's largest, as lib/reference.py does, and
    (b) how far each of the program's choices lies under the reference's own
    cut (`routing_margins`). `routing` [n_moe, T', top_k + 1] covers the
    positions the program computed (T' = prompt + answer - 1); the reference
    routes the rest (the last token and the padding) itself."""
    sp = spec_of(hp)
    seq = list(prompt) + list(answer)
    at = [len(prompt) - 1 + i for i in range(len(answer))]
    n = -(-len(seq) // pad_multiple) * pad_multiple
    padded = seq + [0] * (n - len(seq))
    choices, worst = None, {"expert_steps": 0.0, "group_steps": 0.0,
                            "same_experts": 1.0}
    if routing is not None:
        routing = np.asarray(routing)
        full = np.full((routing.shape[0], n, sp.top_k + 1), -1, np.int32)
        full[:, : routing.shape[1]] = routing
        choices = full[:, :, : sp.top_k]
    norms: Dict[int, Any] = {}

    def on_router(m, view, _experts):
        if routing is None:
            return
        got = routing_margins(sp, view, norms["x"], norms["w"][m], full[m])
        worst["expert_steps"] = max(worst["expert_steps"], got["expert_steps"])
        worst["group_steps"] = max(worst["group_steps"], got["group_steps"])
        worst["same_experts"] = min(worst["same_experts"], got["same_experts"])

    # |x| of a normed hidden state is sqrt(D) up to the norm's weight (ones
    # on seeded weights); |W_r[:, e]| is read per expert layer
    norms["x"] = np.full((n,), np.sqrt(sp.hidden), np.float32)
    norms["w"] = weights.router_norms()
    # an answer the end-of-sequence token cut short still has a position to
    # judge the routing at; one it cut to nothing has no logit to judge
    lg = logits_at(hp, weights, padded, at or [len(prompt) - 1], choices,
                   on_router)
    answer = np.asarray(answer, np.int64)
    top = lg.max(axis=-1)[: len(answer)]
    got = lg[np.arange(len(answer)), answer]
    return {"gaps": (top - got).tolist() or [0.0],
            "max_abs_logit": float(np.abs(lg).max()),
            "argmax_equal": int((lg.argmax(-1)[: len(answer)] == answer).sum()),
            "routing": worst}

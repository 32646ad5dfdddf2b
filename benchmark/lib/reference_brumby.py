"""The plain reference of Brumby-14B: the forward pass written from the
published configuration and the published description of power retention, in
straightforward float32 `jax.numpy`, one sequence at a time from position 0.
It shares no code with `ray_tpu`: no kernel, no feature map, no state, no
chunk, no cache, no snapshot, no batching.

Published layer (pre-norm residual, eps from the config, all layers alike):

    h += Ret(RMSNorm(h));  h += W_down(SiLU(W_gate u) * W_up u), u = RMSNorm(h)

Ret (the quadratic form, which is the definition):
    q = W_q u -> heads x hd;  k, v = W_k u, W_v u -> kv_heads x hd
    q, k RMS-normed per head with a learned weight of hd, then rotary over
    the whole head (theta from the config); query head h reads KV head
    h // (heads / kv_heads)
    gamma_t = log sigmoid(w_g . u_t + b_g) <= 0, one a KV head, float32
    a_tj = exp(gamma_{j+1} + ... + gamma_t) ((q_t . k_j) / sqrt(hd))^2, j <= t
    o_t = sum_j a_tj v_j / (sum_j a_tj + eps);  out = W_o o

computed causally a block of queries at a time, so that 9k positions fit.
What the published keys do not settle is listed in the configuration file's
`assumed`. For the state check alone `direct_state` gives what a recurrence
over the symmetric square of the key must hold after position t:
`sum_j exp(gamma_{j+1..t}) k_j (x) k_j (x) v_j`, `[kv_heads, hd, hd, hd]`.

`hp` is the configuration file's dict. Weights arrive through a view object
(runners/_inside_brumby.ProgramWeightsBrumby, or a test's own):

    weights.embed(tokens) -> [T, D];  weights.final_norm()
    weights.head(lo, hi) -> [D, hi - lo]
    weights.layer(i) -> dict of float32 arrays: attn_norm, wq, wk, wv, q_norm,
        k_norm, wg [D, kv_heads], bg [kv_heads], wo, ffn_norm, w_gate, w_up,
        w_down
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
HEAD_BLOCK = 16384     # vocabulary rows the head is multiplied by at a time
STATE_BLOCK = 512      # positions `direct_state` sums at a time
FFN_ROWS = 1024        # rows the feed-forward layer takes at a time


class Spec(NamedTuple):
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    ret_eps: float
    vocab: int


def spec_of(hp: dict) -> Spec:
    return Spec(hp["hidden_size"], hp["num_hidden_layers"],
                hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"], float(hp["rope_theta"]),
                float(hp["rms_norm_eps"]),
                float(hp.get("retention_eps", 1e-6)), hp["vocab_size"])


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(sp: Spec, x, pos):
    """x [T, heads, hd]; the halves of a head are rotated against each
    other (the Llama and Qwen convention)."""
    half = sp.head_dim // 2
    freqs = sp.theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gate(x, wg, bg, dtype=None):
    """gamma [T, kv_heads]. `dtype`: the second reading, the logit and its
    log sigmoid in that precision."""
    z = x @ wg + bg
    if dtype is not None:
        return jax.nn.log_sigmoid(z.astype(dtype)).astype(jnp.float32)
    return jax.nn.log_sigmoid(z)


def retention_inputs(sp: Spec, x, w):
    """x [T, D] normed -> q [T, heads, hd], k, v [T, kv, hd], gamma [T, kv]."""
    T = x.shape[0]
    pos = jnp.arange(T)
    q = (x @ w["wq"]).reshape(T, sp.heads, sp.head_dim)
    k = (x @ w["wk"]).reshape(T, sp.kv_heads, sp.head_dim)
    v = (x @ w["wv"]).reshape(T, sp.kv_heads, sp.head_dim)
    q = rope(sp, rms(q, w["q_norm"], sp.eps), pos)
    k = rope(sp, rms(k, w["k_norm"], sp.eps), pos)
    return q, k, v, gate(x, w["wg"], w["bg"])


def retention(sp: Spec, x, w):
    """The quadratic form over one sequence x [T, D] (T a multiple of the
    query block or shorter than it)."""
    T = x.shape[0]
    q, k, v, gamma = retention_inputs(sp, x, w)
    G = sp.heads // sp.kv_heads
    cum = jnp.cumsum(gamma, axis=0)                                # [T, kv]
    qb = min(QUERY_BLOCK, T)
    assert T % qb == 0, "sequence lengths are multiples of the query block"
    qg = q.reshape(T // qb, qb, sp.kv_heads, G, sp.head_dim)
    pos = jnp.arange(T)

    def block(args):
        qi, qpos, cum_i = args
        s = jnp.einsum("qkgd,jkd->kgqj", qi, k) / jnp.sqrt(
            jnp.float32(sp.head_dim))
        seen = pos[None, :] <= qpos[:, None]                       # [q, j]
        diff = cum_i.T[:, :, None] - cum.T[:, None, :]             # [k, q, j]
        decay = jnp.where(seen[None], jnp.exp(jnp.where(seen[None], diff, 0.0)),
                          0.0)
        a = s * s * decay[:, None]
        num = jnp.einsum("kgqj,jkd->qkgd", a, v)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)              # [q, k, g]
        return num / (den[..., None] + sp.ret_eps)

    o = jax.lax.map(block, (qg, pos.reshape(T // qb, qb),
                            cum.reshape(T // qb, qb, sp.kv_heads)))
    return o.reshape(T, sp.heads * sp.head_dim) @ w["wo"]


def ffn(x, w):
    """A block of rows at a time: the hidden layer of 9k rows would be
    0.6 GB three times over."""
    def rows(xb):
        return (jax.nn.silu(xb @ w["w_gate"]) * (xb @ w["w_up"])) @ w["w_down"]

    T = x.shape[0]
    if T <= FFN_ROWS or T % FFN_ROWS:
        return rows(x)
    return jax.lax.map(rows, x.reshape(T // FFN_ROWS, FFN_ROWS, -1)).reshape(
        x.shape)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(sp: Spec, h, w, activations=None):
    def rounded(x):
        if activations is None:
            return x
        return x.astype(activations).astype(jnp.float32)

    h = rounded(h + retention(sp, rounded(rms(h, w["attn_norm"], sp.eps)), w))
    return rounded(h + ffn(rounded(rms(h, w["ffn_norm"], sp.eps)), w))


def hidden_states(hp: dict, weights, tokens: Sequence[int], activations=None):
    """The last layer's output [T, D] for one sequence from position 0.
    `activations`: the second reading, the residual stream and every normed
    input rounded to that dtype."""
    sp = spec_of(hp)
    with jax.default_matmul_precision("highest"):
        h = weights.embed(jnp.asarray(tokens, jnp.int32))
        for i in range(sp.layers):
            h = _layer(sp, h, weights.layer(i), activations)
        return rms(h, weights.final_norm(), sp.eps)


def logits_at(hp: dict, weights, tokens: Sequence[int], at: Sequence[int],
              **kw) -> np.ndarray:
    """Float32 logits [len(at), V] at positions `at`, the head a block of
    the vocabulary at a time."""
    sp = spec_of(hp)
    h = hidden_states(hp, weights, tokens, **kw)[jnp.asarray(at)]
    with jax.default_matmul_precision("highest"):
        return np.concatenate(
            [np.asarray(h @ weights.head(lo, min(lo + HEAD_BLOCK, sp.vocab)))
             for lo in range(0, sp.vocab, HEAD_BLOCK)], axis=-1)


def teacher_forced_gaps(hp: dict, weights, prompt: Sequence[int],
                        answer: Sequence[int], pad_multiple: int = 256,
                        **kw) -> Dict[str, Any]:
    """Feed prompt + answer through the reference from position 0 and
    measure how far the returned token's reference logit lies under each
    answer position's largest. `kw`: `hidden_states`' second reading."""
    seq = list(prompt) + list(answer)
    at = [len(prompt) - 1 + i for i in range(len(answer))]
    n = -(-len(seq) // pad_multiple) * pad_multiple
    lg = logits_at(hp, weights, seq + [0] * (n - len(seq)),
                   at or [len(prompt) - 1], **kw)
    answer = np.asarray(answer, np.int64)
    top = lg.max(axis=-1)[: len(answer)]
    got = lg[np.arange(len(answer)), answer]
    return {"gaps": (top - got).tolist() or [0.0],
            "max_abs_logit": float(np.abs(lg).max()),
            "argmax_equal": int((lg.argmax(-1)[: len(answer)] == answer).sum())}


# ---------------------------------------------------------------------------
# the state a recurrence must hold, and the gate, on the program's own inputs
# ---------------------------------------------------------------------------


def direct_state(k, v, gamma, state_dtype=None, start=None) -> np.ndarray:
    """`sum_j exp(gamma_{j+1} + ... + gamma_{T-1}) k_j (x) k_j (x) v_j` for
    one layer: k, v [T, kv, hd], gamma [T, kv] -> [kv, hd, hd, hd], a block
    of positions at a time. `state_dtype`: the second reading, the sum kept
    as a state from `start` [kv, hd, hd, hd] that is decayed, added to and
    rounded to that dtype after every position."""
    k, v, gamma = (jnp.asarray(a, jnp.float32) for a in (k, v, gamma))
    T, KV, hd = k.shape
    with jax.default_matmul_precision("highest"):
        if state_dtype is not None:
            def step(S, row):
                kt, vt, gt = row
                S = jnp.exp(gt)[:, None, None, None] * S + jnp.einsum(
                    "ka,kb,kd->kabd", kt, kt, vt)
                # not a pair of casts, which a compiler may take out
                bits = jnp.finfo(state_dtype)
                return jax.lax.reduce_precision(S, bits.nexp, bits.nmant), None

            S0 = (jnp.zeros((KV, hd, hd, hd), jnp.float32) if start is None
                  else jnp.asarray(start, jnp.float32))
            return np.asarray(jax.lax.scan(step, S0, (k, v, gamma))[0])
        # the running sum of the log gates on the host, in float64: over 9k
        # positions a head that forgets fast reaches -1,000 and more, where
        # a float32 sum is good to 1e-4 and the weights to no better
        cum = np.cumsum(np.asarray(gamma, np.float64), axis=0)
        w = jnp.asarray(np.exp(cum[-1:] - cum), jnp.float32)       # [T, kv]
        out = jnp.zeros((KV, hd * hd, hd), jnp.float32)
        for lo in range(0, T, STATE_BLOCK):
            kb = k[lo:lo + STATE_BLOCK]
            kk = (kb[..., :, None] * kb[..., None, :]).reshape(
                kb.shape[0], KV, hd * hd)
            out = out + jnp.einsum(
                "jkx,jkd->kxd", kk,
                v[lo:lo + STATE_BLOCK] * w[lo:lo + STATE_BLOCK, :, None])
        return np.asarray(out.reshape(KV, hd, hd, hd))


def recurrence_inputs(chunks: Sequence[Dict[str, Any]],
                      steps: Optional[Dict[str, Any]] = None
                      ) -> Dict[str, np.ndarray]:
    """What `PagedEngine.check_routing(..., mechanisms=True)` recorded of a
    slot's recurrence, in the order it ran: the rows of each prompt chunk
    (a dict a chunk of [layers, n, ...]), then one row a decode step
    ([steps, layers, ...]), as one sequence [tokens, layers, ...] a name."""
    out = {}
    for name in ("k", "v", "gamma", "gate_x"):
        parts = [np.moveaxis(np.asarray(c[name]), 1, 0) for c in chunks]
        if steps is not None and name in steps:
            parts.append(np.asarray(steps[name]))
        out[name] = np.concatenate(parts, axis=0)
    return out


def relative_error(got, want) -> float:
    """The worst KV head's |got - want| / |want| (Frobenius)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = tuple(range(1, got.ndim))
    return float(np.max(np.sqrt(((got - want) ** 2).sum(axes))
                        / np.maximum(np.sqrt((want ** 2).sum(axes)), 1e-30)))


def mechanism_readings(rows: Dict[str, np.ndarray], state: np.ndarray,
                       gates: Sequence[Any], low_rows: int
                       ) -> Dict[str, float]:
    """`rows`: the program's own k, v, gamma and the gate's input at every
    position of one sequence from position 0 ([T, layers, ...],
    `recurrence_inputs`); `state`: what the program's slot held after the
    last of them, unpacked to [layers, kv, hd, hd, hd]; `gates`: (w_g, b_g)
    of each layer, float32.

    `state_error`: the worst layer's and KV head's relative error of the
    program's state against `direct_state` on the same inputs.
    `gamma_error`: the worst relative error of the program's log gate against
    the float32 gate on the program's own normed input. Beside each, under
    `..._bf16`, the second reading: the last `low_rows` positions run as a
    recurrence whose state is rounded to bf16 after every position (first
    layer), and the gate computed in bf16."""
    out = {"state_error": 0.0, "state_error_bf16": 0.0, "gamma_error": 0.0,
           "gamma_error_bf16": 0.0, "state_rows": int(rows["k"].shape[0])}
    for m, (wg, bg) in enumerate(gates):
        k, v, gm = (rows[n][:, m] for n in ("k", "v", "gamma"))
        want = direct_state(k, v, gm)
        out["state_error"] = max(out["state_error"],
                                 relative_error(state[m], want))
        if m == 0 and low_rows:
            before = direct_state(k[:-low_rows], v[:-low_rows], gm[:-low_rows])
            low = direct_state(k[-low_rows:], v[-low_rows:], gm[-low_rows:],
                               state_dtype=jnp.bfloat16, start=before)
            out["state_error_bf16"] = relative_error(low, want)
        x = jnp.asarray(rows["gate_x"][:, m], jnp.float32)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(gate(x, wg, bg), np.float64)
            low = np.asarray(gate(x, wg, bg, jnp.bfloat16), np.float64)
        scale = np.maximum(np.abs(ref), 1e-30)
        out["gamma_error"] = max(out["gamma_error"], float(
            np.max(np.abs(np.asarray(gm, np.float64) - ref) / scale)))
        out["gamma_error_bf16"] = max(out["gamma_error_bf16"], float(
            np.max(np.abs(low - ref) / scale)))
    return out

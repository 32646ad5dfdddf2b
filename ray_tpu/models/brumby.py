"""Brumby family (`brumby`): Qwen3's dense decoder with every attention layer
replaced by power retention (`ops/power_retention.py`). No softmax layer, so
nothing of a sequence is kept by position: all a sequence has is one float32
state a layer.

Published layer, pre-norm residual, all layers alike:

    h += Ret(RMSNorm(h));  h += W_down(SiLU(W_gate u) * W_up u), u = RMSNorm(h)

Ret  q = W_q u in heads x hd; k, v = W_k u, W_v u in kv_heads x hd; q and k
     RMS-normed per head with a learned weight of hd (Qwen3's q/k norm), then
     rotary over the whole head (`models/llama.py::rope_tables`); query head h
     reads KV head h // (heads / kv_heads). Per KV head a log gate
         gamma_t = log sigmoid(w_g . u_t + b_g) <= 0        (float32)
     and, with a_tj = exp(gamma_{j+1} + ... + gamma_t) ((q_t . k_j) /
     sqrt(hd))^2 for j <= t (a_tt has no gate),
         o_t = sum_j a_tj v_j / (sum_j a_tj + eps);  out = W_o o.

`ret_sequence` is that quadratic form and what `forward` computes. The
engine runs its recurrence: a state `S [phi_width(hd), hd]` and a normaliser
`Z [hd, hd]` a KV head, shared by the head's group of queries
(`ret_block`; `llm/_brumby_steps.py` owns the caches and builds the jitted
step from the blocks here). The parameters are stacked on a leading layer
axis and the steps scan them, as Llama's do.

Precision: weights and activations in `dtype`; the gate, the state, the
normaliser, the powers and the norms' statistics in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import rms_norm, rope_tables
from ray_tpu.ops import power_retention as ret_ops


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    head_dim: int = 128
    ffn_dim: int = 17408
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    ret_eps: float = 1e-6          # added to the normaliser
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def brumby_14b(cls, **kw) -> "BrumbyConfig":
        """Published widths; keyword arguments override any field."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "BrumbyConfig":
        """Test size: 2 layers, 4 query heads on 2 KV heads of 16."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, ffn_dim=128, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32), **kw})

    @property
    def group(self) -> int:
        """Query heads that read one KV head, and share its state."""
        return self.n_heads // self.n_kv_heads

    @property
    def state_width(self) -> int:
        return ret_ops.phi_width(self.head_dim)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: BrumbyConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, N(0, 1/fan_in), stacked over the layers. The
    gate's bias is drawn U(2, 10) a KV head: with a zero-mean w_g . u of unit
    spread a head then forgets over a few positions (b = 2) to tens of
    thousands (b = 10), as a trained gate spreads its heads; at b = 0 every
    head would forget in under two."""
    pd, D, L = cfg.param_dtype, cfg.dim, cfg.n_layers
    hd, F = cfg.head_dim, cfg.ffn_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    keys = iter(jax.random.split(key, 16))

    def dense(fan_in, shape, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    layers = {
        "ln1": jnp.ones((L, D), pd), "ln2": jnp.ones((L, D), pd),
        # columns [q | k | v]: one matmul a layer, as Llama's step packs
        "wqkv": dense(D, (L, D, nq + 2 * nkv)),
        "q_norm": jnp.ones((L, hd), pd), "k_norm": jnp.ones((L, hd), pd),
        "wg": dense(D, (L, D, cfg.n_kv_heads)),
        "bg": jax.random.uniform(next(keys), (L, cfg.n_kv_heads),
                                 jnp.float32, 2.0, 10.0),
        "wo": dense(nq, (L, nq, D)),
        "w1": dense(D, (L, D, F)), "w3": dense(D, (L, D, F)),
        "w2": dense(F, (L, F, D)),
    }
    return {"tok_emb": dense(D, (cfg.vocab_size, D)), "layers": layers,
            "norm": jnp.ones((D,), pd),
            "lm_head": dense(D, (D, cfg.vocab_size))}


# ---------------------------------------------------------------------------
# the retention layer
# ---------------------------------------------------------------------------


def _rope(x, cos, sin):
    """x [N, heads, hd] float32; cos, sin [N, hd / 2]."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def ret_inputs(cfg: BrumbyConfig, p, x, positions):
    """From the normed input x [N, D] at `positions` [N]: q [N, KV, G, hd]
    (normed, rotated, and scaled by hd^-1/2: the power's scale is inside
    it), k, v [N, KV, hd], the log gate gamma [N, KV] and the gate itself,
    exp(gamma), float32."""
    N, hd, KV = x.shape[0], cfg.head_dim, cfg.n_kv_heads
    dt = cfg.dtype
    nq, nkv = cfg.n_heads * hd, KV * hd
    qkv = x @ p["wqkv"].astype(dt)
    q = qkv[:, :nq].reshape(N, cfg.n_heads, hd)
    k = qkv[:, nq:nq + nkv].reshape(N, KV, hd)
    v = qkv[:, nq + nkv:].reshape(N, KV, hd).astype(jnp.float32)
    cos, sin = rope_tables(cfg, positions)
    q = _rope(rms_norm(q, p["q_norm"], cfg.norm_eps).astype(jnp.float32),
              cos, sin)
    k = _rope(rms_norm(k, p["k_norm"], cfg.norm_eps).astype(jnp.float32),
              cos, sin)
    # ((q . k) / sqrt(hd))^2 = ((q / sqrt(hd)) . k)^2
    q = (q * hd ** -0.5).reshape(N, KV, cfg.group, hd)
    # the gate's logit is accumulated and kept in float32. A decode row
    # multiplies the state by sigmoid(z) itself and not by exp(log sigmoid
    # (z)): near 1 the chip's exp is good to 1e-6 of its result, which 400
    # steps of a head that forgets nothing add up to 5e-4 of the state
    # (PERF.md section 6, PR 48); 1 / (1 + e^-z) errs by 1e-6 of e^-z
    z = jnp.dot(x, p["wg"].astype(dt),
                preferred_element_type=jnp.float32) + p["bg"]
    return q, k, v, jax.nn.log_sigmoid(z), jax.nn.sigmoid(z)


def ret_output(cfg: BrumbyConfig, p, o):
    """o [N, KV, G, hd] float32 -> [N, D]."""
    return o.reshape(o.shape[0], -1).astype(cfg.dtype) @ p["wo"].astype(cfg.dtype)


def ret_block(cfg: BrumbyConfig, p, x, positions, B: int, step, chunk=None):
    """A retention layer on the rows of one engine step: x [B + C, D]
    normed, one position of each of B slots, then the C rows of one chunk of
    one sequence (C = 0 without `chunk`), each at its `positions`, through the
    layer's matmuls as one batch. `step(q, k, v, gate)` -> o [B, KV, G, hd]
    moves the slots' states, which the caller owns; `chunk` = (n, S [KV, W,
    hd], Z [KV, hd, hd]): the first n of the C rows are real and start from
    that state.

    Returns (y [B + C, D], the chunk's S and Z after its row n - 1 (None,
    None without one), the recurrence's inputs (q, k, v, gamma) of all the
    rows, for a check to replay)."""
    with jax.named_scope("retention"):
        q, k, v, gamma, gate = ret_inputs(cfg, p, x, positions)
        o = step(q[:B], k[:B], v[:B], gate[:B])
        S = Z = None
        if chunk is not None:
            n, S, Z = chunk
            valid = (jnp.arange(x.shape[0] - B) < n)[:, None]
            # a padded row leaves the state as it was
            o_c, S, Z = ret_ops.retention_chunked(
                q[B:], jnp.where(valid[..., None], k[B:], 0.0), v[B:],
                jnp.where(valid, gamma[B:], 0.0), S, Z, cfg.ret_eps)
            o = jnp.concatenate([o, o_c])
        return ret_output(cfg, p, o), S, Z, (q, k, v, gamma)


def ret_sequence(cfg: BrumbyConfig, p, x, valid):
    """One sequence from position 0, the quadratic form, no state: x [T, D]
    normed; valid [T] (padding trails)."""
    with jax.named_scope("retention"):
        T = x.shape[0]
        q, k, v, gamma, _ = ret_inputs(cfg, p, x, jnp.arange(T))
        k = jnp.where(valid[:, None, None], k, 0.0)
        o = ret_ops.retention_quadratic(q, k, v, gamma, cfg.ret_eps)
        return ret_output(cfg, p, o)


def ffn(cfg: BrumbyConfig, p, x):
    with jax.named_scope("ffn"):
        dt = cfg.dtype
        gate = jax.nn.silu(x @ p["w1"].astype(dt))
        return (gate * (x @ p["w3"].astype(dt))) @ p["w2"].astype(dt)


# ---------------------------------------------------------------------------
# the whole model on one sequence
# ---------------------------------------------------------------------------


def forward(cfg: BrumbyConfig, params, tokens, plen=None):
    """tokens [T] (one sequence) -> logits [T, V] float32, by the quadratic
    form. `plen` (default T) marks the trailing padding."""
    T = tokens.shape[0]
    valid = jnp.arange(T) < (T if plen is None else plen)
    dt = cfg.dtype
    h = params["tok_emb"].astype(dt)[tokens]

    def layer(h, p):
        h = h + ret_sequence(cfg, p, rms_norm(h, p["ln1"], cfg.norm_eps), valid)
        return h + ffn(cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps)), None

    h, _ = jax.lax.scan(layer, h, params["layers"])
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

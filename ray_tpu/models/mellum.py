"""Mellum 2 family (`mellum`): a softmax-routed mixture of experts under
grouped-query attention of two kinds, three sliding-window layers to each
full one, each kind with a rotary embedding of its own.

Published layer l, pre-norm residual, no bias anywhere:

    h += Attn_l(RMSNorm(h));  h += MoE(RMSNorm(h));  logits = W_head RMSNorm(h_L)

Attn q = W_q u in heads x hd; k, v = W_k u, W_v u in kv_heads x hd, with hd
     (128) its own number and not dim / heads; q and k RMS-normed per head
     with a learned weight of hd; rotary of the layer's kind over the whole
     head in halves (`models/llama.py::apply_rope`'s convention);
     softmax(q k^T / sqrt(hd) + mask) v, query head h on KV head h // group;
     W_o.
Kind `layer_types[l]`: "window" (`sliding_attention`) where l % 4 != 3, row
     i sees the keys i - sliding_window < j <= i: itself and the
     sliding_window - 1 before it; "full" where l % 4 == 3, row i sees j <= i.
Rope window layers: inv_freq_i = theta^(-2i/hd). Full layers: YaRN
     (`yarn_inv_freq`): the slow frequencies divided by `yarn_factor`, the
     fast ones kept, a linear ramp between the dimensions `low` and `high`,
     and cos and sin multiplied by `yarn_attention_factor` on q and k alike,
     so a score carries its square.
MoE  z = W_r u in float32; p = softmax(z) over all the experts; the top_k
     largest; weights p_i / sum of the chosen p_j (`norm_topk_prob`);
     y = sum w_i W2_i (silu(W1_i u) * W3_i u). No shared expert, no bias, no
     groups. The experts run through `models/ling.py::moe_held`, used and
     not copied, with this family's `route`.

The blocks here are the model's mathematics for one sequence (`forward`) and
for the rows of one engine step (`attn_project`, `attn_output`);
`llm/_mellum_steps.py` builds the engine's jitted step from them and owns the
caches. The parameters are a list of per-layer dicts and the steps unroll
them, as Ling's and Solar's do.

Precision: weights and activations in `dtype`; the router, its softmax, the
norms' statistics, the rotary's angles and the attention's softmax in
float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import ling
from ray_tpu.models.llama import rms_norm

NEG_INF = -1e30
QUERY_BLOCK = 512
WINDOW, FULL = "window", "full"


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    dim: int = 2304
    n_layers: int = 28
    # published indices of the layers kept (None: 0..n_layers-1); the index
    # decides the kind of attention: full where id % full_period is the
    # period's last
    layer_ids: Optional[Tuple[int, ...]] = None
    full_period: int = 4
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128            # not dim / n_heads (72)
    sliding_window: int = 1024
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782   # 0.1 ln 16 + 1
    norm_eps: float = 1e-6
    moe_ffn_dim: int = 896
    n_experts: int = 64
    top_k: int = 8
    max_seq_len: int = 131072
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # what `ling.moe_held` reads of a config: every expert is held here
    held_start = 0

    @property
    def n_held(self) -> int:
        return self.n_experts

    @classmethod
    def mellum2_12b(cls, **kw) -> "MellumConfig":
        """Published widths; keyword arguments override any field."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "MellumConfig":
        """Test size: two periods (window x 3, full, twice), a window of 8,
        8 experts of which a token takes 2, heads of 16 where dim / heads is
        8. YaRN's range scaled with the rest: 16 positions stretched by 4."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=8, n_heads=8, n_kv_heads=2,
            head_dim=16, sliding_window=8, rope_theta=10000.0,
            yarn_factor=4.0, yarn_original_len=16, yarn_beta_fast=4.0,
            yarn_beta_slow=1.0,
            yarn_attention_factor=0.1 * math.log(4.0) + 1.0,
            moe_ffn_dim=32, n_experts=8, top_k=2, max_seq_len=512,
            dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    def kinds(self) -> List[str]:
        """The attention kind of each kept layer."""
        ids = self.layer_ids or tuple(range(self.n_layers))
        assert len(ids) == self.n_layers, "layer_ids must name n_layers layers"
        last = self.full_period - 1
        return [FULL if pub % self.full_period == last else WINDOW
                for pub in ids]

    @property
    def window_layers(self) -> int:
        return sum(kind == WINDOW for kind in self.kinds())

    @property
    def full_layers(self) -> int:
        return sum(kind == FULL for kind in self.kinds())


# ---------------------------------------------------------------------------
# rotary embeddings, one a kind of layer
# ---------------------------------------------------------------------------


def yarn_range(cfg: MellumConfig) -> Tuple[int, int]:
    """(low, high): the dimensions between which YaRN's ramp runs. corr(r)
    is the dimension whose wavelength makes r turns over the original
    length; the range is truncated to whole dimensions (floor and ceil)."""
    d, L0 = cfg.head_dim, cfg.yarn_original_len

    def corr(turns: float) -> float:
        return d * math.log(L0 / (2 * math.pi * turns)) / (
            2 * math.log(cfg.rope_theta))

    low = max(math.floor(corr(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.yarn_beta_slow)), d - 1)
    return low, high


def inv_freq(cfg: MellumConfig, kind: str) -> Tuple[np.ndarray, float]:
    """(the hd / 2 rotary frequencies of a layer of `kind`, float32; what
    its cos and sin are multiplied by)."""
    half = cfg.head_dim // 2
    i = np.arange(half, dtype=np.float64)
    plain = cfg.rope_theta ** (-i / half)
    if kind == WINDOW:
        return plain.astype(np.float32), 1.0
    low, high = yarn_range(cfg)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = plain * ((1.0 - ramp) + ramp / cfg.yarn_factor)
    return freq.astype(np.float32), float(cfg.yarn_attention_factor)


def rope(cfg: MellumConfig, kind: str, x, positions):
    """x [N, heads, hd] at `positions` [N] -> float32, rotated in halves."""
    freq, factor = inv_freq(cfg, kind)
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: MellumConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, N(0, 1/fan_in), but the per-head norms of q
    and k, drawn U(1, 2) a channel: a score is then a sum over 128 channels
    of spread ~2.3 (3.8 under YaRN's factor squared) and not ~1, so that a
    softmax has keys that matter and a lost or misplaced key shows in the
    logits, as a trained head's does."""
    pd, D, hd = cfg.param_dtype, cfg.dim, cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    F, n = cfg.moe_ffn_dim, cfg.n_experts
    keys = iter(jax.random.split(key, 12 * cfg.n_layers + 4))

    def dense(fan_in, shape, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    def spread(shape):
        return jax.random.uniform(
            next(keys), shape, jnp.float32, 1.0, 2.0).astype(pd)

    layers = []
    for _ in cfg.kinds():
        layers.append({
            "ln1": jnp.ones((D,), pd), "ln2": jnp.ones((D,), pd),
            # columns [q | k | v]: one matmul a layer, as Llama's step packs
            "wqkv": dense(D, (D, nq + 2 * nkv)),
            "q_norm": spread((hd,)), "k_norm": spread((hd,)),
            "wo": dense(nq, (nq, D)),
            "router": dense(D, (D, n), jnp.float32),
            "e_w1": dense(D, (n, D, F)), "e_w3": dense(D, (n, D, F)),
            "e_w2": dense(F, (n, F, D))})
    return {"tok_emb": dense(D, (cfg.vocab_size, D)), "layers": layers,
            "norm": jnp.ones((D,), pd),
            "lm_head": dense(D, (D, cfg.vocab_size))}


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attn_project(cfg: MellumConfig, kind: str, p, x, positions):
    """x [N, D] normed, at `positions` [N] -> q [N, heads, hd], k, v [N,
    kv_heads, hd] (dtype): one matmul over the packed leaf, q and k normed
    per head and rotated as a layer of `kind` rotates them."""
    N, hd, dt = x.shape[0], cfg.head_dim, cfg.dtype
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    qkv = x @ p["wqkv"].astype(dt)
    q = rms_norm(qkv[:, :nq].reshape(N, cfg.n_heads, hd), p["q_norm"],
                 cfg.norm_eps)
    k = rms_norm(qkv[:, nq:nq + nkv].reshape(N, cfg.n_kv_heads, hd),
                 p["k_norm"], cfg.norm_eps)
    v = qkv[:, nq + nkv:].reshape(N, cfg.n_kv_heads, hd)
    return (rope(cfg, kind, q, positions).astype(dt),
            rope(cfg, kind, k, positions).astype(dt), v)


def attn_output(cfg: MellumConfig, p, o):
    """o [N, heads, hd] -> [N, D]."""
    dt = cfg.dtype
    return o.reshape(o.shape[0], -1).astype(dt) @ p["wo"].astype(dt)


def attn_sequence(cfg: MellumConfig, kind: str, p, x, valid,
                  window: Optional[int] = None):
    """One sequence from position 0, no cache: x [T, D] normed; valid [T]
    (padding trails). `window` (default `cfg.sliding_window`) is what a
    window layer sees; a full layer sees all before it."""
    with jax.named_scope("attn_" + kind):
        T, hd = x.shape[0], cfg.head_dim
        rep = cfg.n_heads // cfg.n_kv_heads
        pos = jnp.arange(T)
        q, k, v = attn_project(cfg, kind, p, x, pos)
        reach = T if kind == FULL else (window or cfg.sliding_window)
        qb = min(QUERY_BLOCK, T)
        assert T % qb == 0, "sequence lengths are multiples of the query block"
        qg = q.reshape(T // qb, qb, cfg.n_kv_heads, rep, hd)

        def block(args):
            qi, qpos = args
            s = jnp.einsum("qkrd,wkd->krqw", qi, k,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            seen = ((pos[None, :] <= qpos[:, None])
                    & (pos[None, :] > qpos[:, None] - reach) & valid[None, :])
            s = jnp.where(seen, s, NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            return jnp.einsum("krqw,wkd->qkrd", probs, v)

        o = lax.map(block, (qg, pos.reshape(T // qb, qb)))
        return attn_output(cfg, p, o.reshape(T, cfg.n_heads, hd))


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------


def router_probs(cfg: MellumConfig, p, x, dtype=jnp.float32):
    """x [N, D] -> softmax over all the experts [N, n_experts], float32 (a
    check plants a lower `dtype`)."""
    z = jnp.dot(x.astype(dtype), p["router"].astype(dtype),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=dtype)
    return jax.nn.softmax(z.astype(jnp.float32), axis=-1)


def route(cfg: MellumConfig, p, x):
    """`ling.route`'s result for this family's router: (experts [N, top_k]
    int32, weights [N, top_k] float32: the chosen probabilities over their
    sum, kept [N] int32: one group of all the experts, always kept, the
    softmax [N, n_experts] float32)."""
    probs = router_probs(cfg, p, x)
    chosen, experts = lax.top_k(probs, cfg.top_k)
    weights = chosen / chosen.sum(-1, keepdims=True)
    return (experts.astype(jnp.int32), weights,
            jnp.ones((x.shape[0],), jnp.int32), probs)


def moe(cfg: MellumConfig, p, x, live):
    """The expert layer on normed x [N, D]: `ling.moe_held`'s result."""
    return ling.moe_held(cfg, p, x, live, shared=False, routing=route)


# ---------------------------------------------------------------------------
# the whole model on one sequence
# ---------------------------------------------------------------------------


def forward(cfg: MellumConfig, params, tokens, plen=None, window=None):
    """tokens [T] (one sequence; T at most 512 or a multiple of it) ->
    logits [T, V] float32. `plen` (default T) marks the trailing padding;
    `window` is `attn_sequence`'s."""
    T = tokens.shape[0]
    valid = jnp.arange(T) < (T if plen is None else plen)
    dt = cfg.dtype
    h = params["tok_emb"].astype(dt)[tokens]
    for kind, p in zip(cfg.kinds(), params["layers"]):
        h = h + attn_sequence(
            cfg, kind, p, rms_norm(h, p["ln1"], cfg.norm_eps), valid, window)
        h = h + moe(cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps), valid)[0]
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

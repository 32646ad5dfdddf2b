"""Ling-3.0-flash family (`bailing_hybrid`): delta-rule linear-attention
layers (KDA) beside latent-attention layers (MLA), a dense SwiGLU in the
leading layers and shared + sigmoid-routed experts after them.

The blocks here are the model's mathematics for one sequence (prefill) and
for a batch of single positions (decode); `ray_tpu/llm/_ling_steps.py` builds
the engine's jitted steps from them and owns the caches. The layers differ in
kind, so the parameters are a list of per-layer dicts and the steps unroll
them (7 layers in the benchmark's cut): no stacked `lax.scan` as in
`models/llama.py`.

**Held experts.** An expert layer is told which experts this chip holds,
`[held_start, held_start + n_held)`. It routes over all `n_experts`, computes
the part of the result its own experts give, for every token routed to them
(rows sorted by expert, a grouped SwiGLU, no capacity, no dropped token), adds
the shared expert, and that partial sum goes on to the next layer. With every
expert held it is the whole layer. On one chip the layer runs without its
exchange; nothing stands in for the absent chips.

Precision: weights and activations in `dtype`; the router and its scores,
the KDA gates, state and recurrence, softmax and the norms in float32.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import rms_norm
from ray_tpu.ops import kda as kda_ops
from ray_tpu.ops.grouped_ffn import grouped_ffn

_HI = lax.Precision.HIGHEST
NEG_INF = -1e30
MLA_QUERY_BLOCK = 512
BALANCE_TOKENS = 1024      # seeded tokens `balance_expert_bias` routes ...
BALANCE_STEPS = 300        # ... and its updates of an expert layer's bias


@dataclass(frozen=True)
class LingConfig:
    vocab_size: int = 157184
    dim: int = 2560
    n_layers: int = 42
    # published indices of the layers kept (None: 0..n_layers-1); the index
    # decides the kind of attention: MLA where (id + 1) % layer_group_size
    # == 0, KDA elsewhere
    layer_ids: Optional[Tuple[int, ...]] = None
    layer_group_size: int = 6
    first_k_dense: int = 2         # leading layers with a dense FFN
    n_heads: int = 32
    head_dim: int = 128            # KDA key and value width per head
    conv_kernel: int = 4
    kda_gate_low: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    # False: the rotary turns channel i with i + rope/2 (`rope_half`); True:
    # the adjacent pair (2i, 2i + 1) (`rope_pairs`)
    rope_interleave: bool = False
    norm_eps: float = 1e-6
    ffn_dim: int = 6144            # dense layers
    moe_ffn_dim: int = 768         # each routed expert and the shared one
    n_experts: int = 512           # the router's width
    n_group: int = 8
    topk_group: int = 4
    top_k: int = 8
    routed_scale: float = 2.5
    held_start: int = 0            # this chip's experts: [start, start + n)
    n_held: int = 512
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def ling3_flash(cls, **kw) -> "LingConfig":
        """Published widths; keyword arguments override any field."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LingConfig":
        """Test size: dense-KDA, KDA, KDA, MLA; 16 experts in 4 groups."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=4, layer_group_size=4,
            first_k_dense=1, n_heads=4, head_dim=16, kv_lora_rank=32,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, rope_theta=1e4,
            ffn_dim=128, moe_ffn_dim=32, n_experts=16, n_group=4,
            topk_group=2, top_k=2, n_held=16, max_seq_len=512,
            dtype=jnp.float32, param_dtype=jnp.float32), **kw})

    def kinds(self) -> List[Tuple[str, str]]:
        """[(attention kind, ffn kind)] per kept layer."""
        ids = self.layer_ids or tuple(range(self.n_layers))
        assert len(ids) == self.n_layers, "layer_ids must name n_layers layers"
        return [("mla" if (pub + 1) % self.layer_group_size == 0 else "kda",
                 "dense" if i < self.first_k_dense else "moe")
                for i, pub in enumerate(ids)]

    @property
    def kda_layers(self) -> int:
        return sum(a == "kda" for a, _ in self.kinds())

    @property
    def mla_layers(self) -> int:
        return sum(a == "mla" for a, _ in self.kinds())

    @property
    def moe_layers(self) -> int:
        return sum(f == "moe" for _, f in self.kinds())

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        """A cached latent's width in memory: `latent_dim` rounded up to the
        TPU's 128 lanes, the tail zero (576 -> 640). At 576 the compiler
        lays the pool out with the blocks innermost and copies it whole,
        there and back, in every step; a multiple of 128 is also what the
        paged-attention kernel takes."""
        return -(-self.latent_dim // 128) * 128

    @property
    def conv_channels(self) -> int:
        return 3 * self.n_heads * self.head_dim


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: LingConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, N(0, 1/fan_in). Drawn so that every mechanism
    is exercised: the expert bias N(0, 0.02); the decay's `A_log` = log
    U(0.5, 2) per head and `dt_bias` = U(-6, -1) per channel, which spreads
    a channel's memory from a few positions to thousands."""
    pd, D, H, dk = cfg.param_dtype, cfg.dim, cfg.n_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32 * cfg.n_layers + 8))

    def dense(fan_in, shape, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    def uniform(lo, hi, shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    layers = []
    for attn, ffn in cfg.kinds():
        p: Dict[str, Any] = {"ln1": jnp.ones((D,), pd), "ln2": jnp.ones((D,), pd)}
        if attn == "kda":
            p.update(
                wqkv=dense(D, (D, 3 * H * dk)),
                conv=dense(cfg.conv_kernel, (cfg.conv_kernel, 3 * H * dk)),
                wa=dense(D, (D, H * dk)), wbeta=dense(D, (D, H)),
                wg=dense(D, (D, H)),
                A_log=jnp.log(uniform(0.5, 2.0, (H,))),
                dt_bias=uniform(-6.0, -1.0, (H * dk,)),
                o_norm=jnp.ones((dk,), pd), wo=dense(H * dk, (H * dk, D)))
        else:
            qk = cfg.qk_nope_dim + cfg.qk_rope_dim
            p.update(
                wq=dense(D, (D, H * qk)), wkva=dense(D, (D, cfg.latent_dim)),
                kv_norm=jnp.ones((cfg.kv_lora_rank,), pd),
                wkvb=dense(cfg.kv_lora_rank, (
                    cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim))),
                wg=dense(D, (D, H)),
                wo=dense(H * cfg.v_head_dim, (H * cfg.v_head_dim, D)))
        if ffn == "dense":
            F = cfg.ffn_dim
            p.update(w1=dense(D, (D, F)), w3=dense(D, (D, F)),
                     w2=dense(F, (F, D)))
        else:
            F, n = cfg.moe_ffn_dim, cfg.n_held
            p.update(
                router=dense(D, (D, cfg.n_experts), jnp.float32),
                router_bias=0.02 * jax.random.normal(
                    next(keys), (cfg.n_experts,), jnp.float32),
                sh_w1=dense(D, (D, F)), sh_w3=dense(D, (D, F)),
                sh_w2=dense(F, (F, D)),
                e_w1=dense(D, (n, D, F)), e_w3=dense(D, (n, D, F)),
                e_w2=dense(F, (n, F, D)))
        layers.append(p)
    return {"tok_emb": dense(D, (cfg.vocab_size, D)), "layers": layers,
            "norm": jnp.ones((D,), pd),
            "lm_head": dense(D, (D, cfg.vocab_size))}


def seeded_params(cfg: LingConfig, key: jax.Array) -> Dict[str, Any]:
    """What a server without a checkpoint serves (`llm.MODEL_FAMILIES`):
    `init_params`, then every expert layer's bias balanced as training
    leaves it (`balance_expert_bias`). Seeded weights stand in for a trained
    model, and a trained router spreads its tokens over the experts."""
    k_init, k_balance = jax.random.split(key)
    return balance_expert_bias(cfg, init_params(cfg, k_init), k_balance)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def rope_half(x, positions, theta: float):
    """x [..., T, heads, hd] with positions [..., T]: rotate-half form, in
    float32."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rope_pairs(x, positions, theta: float):
    """`rope_half`'s arguments, the published `rope_interleave`: channel 2i
    turns with 2i + 1 by pos * theta^(-2i/hd). The result is laid out in
    halves, [the pairs' first members | their second], on queries and keys
    alike, so no score sees the permutation."""
    return rope_half(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                     positions, theta)


def _rope(cfg):
    return rope_pairs if cfg.rope_interleave else rope_half


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _head_gate(cfg: LingConfig, x, p):
    return jax.nn.sigmoid((x @ p["wg"].astype(cfg.dtype)).astype(jnp.float32))


# ---------------------------------------------------------------------------
# KDA
# ---------------------------------------------------------------------------


def _kda_inputs(cfg: LingConfig, p, x, qkv):
    """From the normed input x [N, D] and the convolved, activated qkv [N,
    3*H*dk] (float32): q, k, v, g [N, H, dk] and beta [N, H], float32."""
    N, H, dk = x.shape[0], cfg.n_heads, cfg.head_dim
    q, k, v = (t.reshape(N, H, dk) for t in jnp.split(qkv, 3, axis=-1))
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    a = (x @ p["wa"].astype(cfg.dtype)).astype(jnp.float32).reshape(N, H, dk)
    a = a + p["dt_bias"].reshape(H, dk)
    g = cfg.kda_gate_low * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[None, :, None] * a)
    beta = jax.nn.sigmoid(
        (x @ p["wbeta"].astype(cfg.dtype)).astype(jnp.float32))
    return q, k, v, g, beta


def _kda_output(cfg: LingConfig, p, x, o):
    """o [N, H, dv] float32 -> [N, D]: norm per head, head-wise gate, W_o."""
    o = rms_norm(o, p["o_norm"], cfg.norm_eps).astype(jnp.float32)
    o = (o * _head_gate(cfg, x, p)[..., None]).astype(cfg.dtype)
    return o.reshape(o.shape[0], -1) @ p["wo"].astype(cfg.dtype)


def kda_prefill(cfg: LingConfig, p, x, valid):
    """One sequence. x [T, D] normed; valid [T] bool (False on padding,
    which must trail). Returns (y [T, D], state [H, dk, dv] after the last
    valid position, tail [K-1, 3*H*dk]: the convolution's inputs at the last
    K-1 valid positions)."""
    with jax.named_scope("kda"):
        T, K = x.shape[0], cfg.conv_kernel
        pre = x @ p["wqkv"].astype(cfg.dtype)                 # [T, 3*H*dk]
        qkv = kda_ops.causal_conv_silu(
            pre, jnp.zeros((K - 1, pre.shape[1]), pre.dtype), p["conv"])
        q, k, v, g, beta = _kda_inputs(cfg, p, x, qkv)
        g = jnp.where(valid[:, None, None], g, 0.0)
        beta = jnp.where(valid[:, None], beta, 0.0)
        state0 = jnp.zeros((cfg.n_heads, cfg.head_dim, cfg.head_dim),
                           jnp.float32)
        o, state = kda_ops.kda_chunked(q, k, v, g, beta, state0)
        plen = jnp.sum(valid.astype(jnp.int32))
        idx = plen - (K - 1) + jnp.arange(K - 1)
        tail = jnp.where((idx >= 0)[:, None],
                         pre[jnp.clip(idx, 0, T - 1)], 0).astype(pre.dtype)
        return _kda_output(cfg, p, x, o), state, tail


def kda_decode(cfg: LingConfig, p, x, state, tail):
    """One position of a batch of slots. x [B, D] normed; state [B, H, dk,
    dv] float32; tail [B, K-1, 3*H*dk]. Returns (y [B, D], state, tail, the
    recurrence's inputs (q, k, v, g, beta) for a check to replay)."""
    with jax.named_scope("kda"):
        pre = x @ p["wqkv"].astype(cfg.dtype)                 # [B, C]
        window = jnp.concatenate([tail, pre[:, None].astype(tail.dtype)], 1)
        qkv = jax.nn.silu(jnp.sum(
            window.astype(jnp.float32)
            * p["conv"].astype(jnp.float32)[None], axis=1))
        q, k, v, g, beta = _kda_inputs(cfg, p, x, qkv)
        o, new = kda_ops.kda_step(q, k, v, g, beta, state)
        return (_kda_output(cfg, p, x, o), new, window[:, 1:],
                (q, k, v, g, beta))


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------


def _mla_q(cfg: LingConfig, p, x, positions):
    """x [N, D], positions [N] -> q_nope [N, H, nope], q_rope [N, H, rope]
    (roped, dtype). Where the layer has a low-rank query (`wqa`), q = W_qb
    RMSNorm(W_qa x); else q = W_q x."""
    N, H = x.shape[0], cfg.n_heads
    if "wqa" in p:
        c_q = rms_norm(x @ p["wqa"].astype(cfg.dtype), p["q_norm"],
                       cfg.norm_eps)
        q = c_q @ p["wqb"].astype(cfg.dtype)
    else:
        q = x @ p["wq"].astype(cfg.dtype)
    q = q.reshape(N, H, cfg.qk_nope_dim + cfg.qk_rope_dim)
    q_r = _rope(cfg)(q[..., cfg.qk_nope_dim:], positions, cfg.rope_theta)
    return q[..., : cfg.qk_nope_dim], q_r.astype(cfg.dtype)


def mla_latents(cfg: LingConfig, p, x, positions):
    """What the cache keeps of a position: [N, rank + rope] = the normed
    latent c and the roped shared key k_r."""
    ckr = x @ p["wkva"].astype(cfg.dtype)
    c = rms_norm(ckr[:, : cfg.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_r = _rope(cfg)(ckr[:, None, cfg.kv_lora_rank:], positions,
                     cfg.rope_theta)[:, 0]
    return jnp.concatenate([c.astype(cfg.dtype), k_r.astype(cfg.dtype)], -1)


def _wkvb(cfg: LingConfig, p):
    """W_kvb as [rank, H, nope] (keys) and [rank, H, v] (values)."""
    w = p["wkvb"].astype(cfg.dtype).reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)
    return w[..., : cfg.qk_nope_dim], w[..., cfg.qk_nope_dim:]


def _mla_output(cfg: LingConfig, p, x, o):
    """o [N, H, v] -> [N, D]: the head-wise gate where the layer has one
    (`wg`), then W_o."""
    if "wg" in p:
        o = o.astype(jnp.float32) * _head_gate(cfg, x, p)[..., None]
    return o.astype(cfg.dtype).reshape(o.shape[0], -1) @ p["wo"].astype(cfg.dtype)


def mla_prefill(cfg: LingConfig, p, x, valid):
    """One sequence from position 0, expanded: keys and values of every head
    are made from the latents. x [T, D]; valid [T]. Returns (y [T, D],
    latents [T, rank + rope])."""
    with jax.named_scope("mla"):
        T, H = x.shape[0], cfg.n_heads
        pos = jnp.arange(T, dtype=jnp.int32)
        q_nope, q_r = _mla_q(cfg, p, x, pos)
        lat = mla_latents(cfg, p, x, pos)
        c, k_r = lat[:, : cfg.kv_lora_rank], lat[:, cfg.kv_lora_rank:]
        wk, wv = _wkvb(cfg, p)
        k_nope = jnp.einsum("tc,chn->thn", c, wk)
        v = jnp.einsum("tc,chv->thv", c, wv)
        scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
        qb = min(MLA_QUERY_BLOCK, T)
        assert T % qb == 0, "prefill lengths are powers of two"

        def block(args):
            qn, qr, qpos = args                               # [qb, ...]
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("qhd,kd->hqk", qr, k_r,
                              preferred_element_type=jnp.float32)) * scale
            seen = (pos[None, :] <= qpos[:, None]) & valid[None, :]
            s = jnp.where(seen[None], s, NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            return jnp.einsum("hqk,khd->qhd", probs, v)

        o = lax.map(block, (q_nope.reshape(T // qb, qb, H, -1),
                            q_r.reshape(T // qb, qb, H, -1),
                            pos.reshape(T // qb, qb)))
        o = o.reshape(T, H, cfg.v_head_dim)
        return _mla_output(cfg, p, x, o), lat


def mla_decode(cfg: LingConfig, p, x, positions, attend):
    """One position of a batch of slots, absorbed: W_kvb's key half goes into
    the query and its value half onto the output, so the latents are scored
    as one key head of `rank + rope` whose values are its first `rank`.
    x [B, D]; positions [B]; `attend(q [B, H, rank + rope] dtype, scale)`
    -> [B, H, rank]: softmax(scale q . latent) over a slot's live latents,
    times their first `rank` values (`attend_latents` on a gathered context,
    or the paged kernel over the pool)."""
    with jax.named_scope("mla"):
        q_nope, q_r = _mla_q(cfg, p, x, positions)
        wk, wv = _wkvb(cfg, p)
        q_abs = jnp.einsum("bhn,chn->bhc", q_nope, wk)
        q = jnp.concatenate([q_abs, q_r], -1)
        o_lat = attend(q, 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim))
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(cfg.dtype), wv)
        return _mla_output(cfg, p, x, o)


def attend_latents(cfg: LingConfig, latents, lengths):
    """`mla_decode`'s attention in plain XLA. latents [B, L, >= rank + rope]
    (each slot's context in order, the current position's included; columns
    past rank + rope are padding); lengths [B] live positions (0: attend
    nothing)."""
    def attend(q, scale):
        lat = latents[..., : cfg.latent_dim]
        s = jnp.einsum("bhd,bld->bhl", q, lat,
                       preferred_element_type=jnp.float32) * scale
        live = jnp.arange(lat.shape[1])[None, :] < lengths[:, None]
        s = jnp.where(live[:, None, :], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
        return jnp.einsum("bhl,blc->bhc", probs,
                          lat[..., : cfg.kv_lora_rank])

    return attend


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------


def router_scores(cfg: LingConfig, p, x):
    """x [N, D] -> sigmoid scores [N, n_experts], float32."""
    return jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), p["router"].astype(jnp.float32),
        precision=_HI))


def select_experts(cfg: LingConfig, s, bias):
    """Scores s [N, E] and the expert bias [E] -> (experts [N, top_k], kept
    [N, n_group] bool): selection on score + bias, a group scored by the sum
    of its two best, `top_k` experts among the `topk_group` kept groups'.
    One group (`n_group` 1) is no grouping: the `top_k` best of all."""
    N, E, G = s.shape[0], cfg.n_experts, cfg.n_group
    sb = s + bias
    if G == 1:
        return lax.top_k(sb, cfg.top_k)[1], jnp.ones((N, 1), bool)
    gs = lax.top_k(sb.reshape(N, G, E // G), 2)[0].sum(-1)    # [N, G]
    kept_idx = lax.top_k(gs, cfg.topk_group)[1]
    kept = jnp.zeros((N, G), bool).at[
        jnp.arange(N)[:, None], kept_idx].set(True)
    admissible = jnp.repeat(kept, E // G, axis=1)
    experts = lax.top_k(jnp.where(admissible, sb, -jnp.inf), cfg.top_k)[1]
    return experts, kept


def route(cfg: LingConfig, p, x):
    """x [N, D] -> (experts [N, top_k] int32, weights [N, top_k] float32,
    kept [N] int32: bit g set where group g was kept, scores [N, n_experts]
    float32). The bias moves the selection only; the weights are the chosen
    scores, normalised."""
    G = cfg.n_group
    s = router_scores(cfg, p, x)
    experts, kept = select_experts(cfg, s, p["router_bias"])
    chosen = jnp.take_along_axis(s, experts, axis=1)
    weights = chosen / chosen.sum(-1, keepdims=True) * cfg.routed_scale
    bits = jnp.sum(kept.astype(jnp.int32) << jnp.arange(G, dtype=jnp.int32),
                   axis=-1)
    return experts.astype(jnp.int32), weights, bits, s


def _grouped_experts(cfg: LingConfig, p, rows, counts):
    """SwiGLU of each row's expert. rows [M, D] sorted by held expert, the
    rows of no held expert last; counts [n_held] rows per expert. Rows past
    sum(counts) come back undefined. On a TPU one kernel that reads each
    touched expert's weights once (`ops/grouped_ffn.py`)."""
    dt = cfg.dtype
    return grouped_ffn(rows, p["e_w1"].astype(dt), p["e_w3"].astype(dt),
                       p["e_w2"].astype(dt), counts)


def moe_held(cfg: LingConfig, p, x, live, shared: bool = True, routing=None):
    """x [N, D] normed; live [N] bool (False: padding or an empty slot,
    which is routed nowhere). `routing` (default `route`, this family's and
    Solar's) is the family's router: `routing(cfg, p, x)` -> `route`'s
    four. Returns (y [N, D], routing [N, top_k + 1]
    int32: the chosen experts and the kept-groups mask, counters [4] int32:
    pairs routed, pairs on held experts, held experts with a row, the most
    rows on one held expert, the router's scores [N, n_experts] float32)."""
    with jax.named_scope("moe"):
        N, k, n = x.shape[0], cfg.top_k, cfg.n_held
        experts, weights, bits, scores = (routing or route)(cfg, p, x)
        local = experts - cfg.held_start
        held = (local >= 0) & (local < n) & live[:, None]
        # the experts' leaves may hold several layers' experts on their
        # leading axis, this layer's the n from `e_first` on (a scan over
        # stacked layers: the grouped kernel then reads an expert where it
        # lies, and a layer's slice of the stack is never copied out)
        first, groups = p.get("e_first", 0), p["e_w1"].shape[0]
        # rows sorted by held expert; what is not held sorts last and
        # belongs to no group, so the grouped SwiGLU never visits it
        flat = jnp.where(held, first + local, groups).reshape(N * k)
        order = jnp.argsort(flat, stable=True)
        counts = jnp.sum(jax.nn.one_hot(flat, groups, dtype=jnp.int32), axis=0)
        dt = cfg.dtype
        out = _grouped_experts(cfg, p, x[order // k], counts)
        w_sorted = jnp.where(held, weights, 0.0).reshape(N * k)[order]
        out = jnp.where((w_sorted > 0)[:, None],
                        out.astype(jnp.float32) * w_sorted[:, None], 0.0)
        # back to the tokens' order: row j of token i is pair i * k + j
        y = out[jnp.argsort(order)].reshape(N, k, -1).sum(axis=1).astype(dt)
        if shared:
            y = y + _swiglu(x, p["sh_w1"].astype(dt), p["sh_w3"].astype(dt),
                            p["sh_w2"].astype(dt))
        counters = jnp.stack([
            k * jnp.sum(live.astype(jnp.int32)), jnp.sum(held.astype(jnp.int32)),
            jnp.sum((counts > 0).astype(jnp.int32)), jnp.max(counts)])
        routing = jnp.concatenate([experts, bits[:, None]], axis=1)
        return y, jnp.where(live[:, None], routing, -1), counters, scores


def ffn(cfg: LingConfig, p, x, live):
    """The layer's feed-forward on normed x [N, D]: (y, routing, counters,
    router scores), the last three None for a dense layer."""
    if "router" in p:
        return moe_held(cfg, p, x, live)
    dt = cfg.dtype
    return _swiglu(x, p["w1"].astype(dt), p["w3"].astype(dt),
                   p["w2"].astype(dt)), None, None, None


# ---------------------------------------------------------------------------
# the whole model on one sequence
# ---------------------------------------------------------------------------


def _attention(cfg: LingConfig, p, x, live):
    """A layer's attention on one whole sequence x [T, D] (normed)."""
    return (kda_prefill(cfg, p, x, live)[0] if "conv" in p
            else mla_prefill(cfg, p, x, live)[0])


def balance_tokens(key):
    """The seeded tokens the balancing routes: printable bytes."""
    return jax.random.randint(key, (BALANCE_TOKENS,), 32, 127)


def balanced_bias(cfg, p, x):
    """An expert layer's bias after `BALANCE_STEPS` updates on its normed
    inputs x [T, D]: after each, b_e moves by a fixed step towards the
    experts that got less than the mean load."""
    s = router_scores(cfg, p, x)
    mean_load = x.shape[0] * cfg.top_k / cfg.n_experts

    def step(i, b):
        experts, _ = select_experts(cfg, s, b)
        load = jnp.sum(jax.nn.one_hot(
            experts.reshape(-1), cfg.n_experts, dtype=jnp.float32), 0)
        # the step shrinks from a tenth of the scores' range to a
        # thousandth, as a learning rate would
        rate = 0.05 * 0.01 ** (i / (BALANCE_STEPS - 1))
        return b + rate * jnp.sign(mean_load - load)

    return lax.fori_loop(0, BALANCE_STEPS, step, p["router_bias"])


def balance_expert_bias(cfg, params, key, attention=None):
    """Set every expert layer's bias the way the published scheme trains it
    (auxiliary-loss-free balancing: after each batch, b_e moves by a fixed
    step towards the experts that got less than the mean load), on
    `BALANCE_TOKENS` seeded byte tokens, layer after layer.

    Why seeded weights need it: a random network's hidden states share a
    large common component, so a random router sends nearly every token to
    the same few groups. Which groups is the seed's luck: the share of pairs
    on the experts a chip holds swung from 22 to 28% with the seed and a
    decode step's time with it (v5e, PR 29), where a trained model's router
    is balanced by this very term.

    `attention(p, x, live)` is a layer's attention on the whole sequence:
    this family's unless another family, whose layers carry the same router
    and experts, gives its own (`models/solar.py`)."""
    if attention is None:
        attention = functools.partial(_attention, cfg)
    T = BALANCE_TOKENS
    tokens = balance_tokens(key)
    live = jnp.ones((T,), bool)
    dt = cfg.dtype
    h = params["tok_emb"].astype(dt)[tokens]
    layers = []
    for p in params["layers"]:
        h = h + attention(p, rms_norm(h, p["ln1"], cfg.norm_eps), live)
        x = rms_norm(h, p["ln2"], cfg.norm_eps)
        if "router" in p:
            p = {**p, "router_bias": balanced_bias(cfg, p, x)}
        h = h + ffn(cfg, p, x, live)[0]
        layers.append(p)
    return {**params, "layers": layers}


def forward(cfg: LingConfig, params, tokens, plen=None):
    """tokens [T] (one sequence; T a power of two or at most 512 when an MLA
    layer is present) -> logits [T, V] float32. `plen` (default T) marks the
    trailing padding."""
    T = tokens.shape[0]
    valid = jnp.arange(T) < (T if plen is None else plen)
    dt = cfg.dtype
    h = params["tok_emb"].astype(dt)[tokens]
    for (attn, _), p in zip(cfg.kinds(), params["layers"]):
        x = rms_norm(h, p["ln1"], cfg.norm_eps)
        if attn == "kda":
            y, _, _ = kda_prefill(cfg, p, x, valid)
        else:
            y, _ = mla_prefill(cfg, p, x, valid)
        h = h + y
        h = h + ffn(cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps), valid)[0]
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

"""Solar-Open2 family (`solar_open2`): delta-rule linear-attention layers
(KDA) beside gated softmax attention without positions (NoPE GQA), every
layer with shared + sigmoid-routed experts.

Published layer l, pre-norm residual:

    h += Attn_l(RMSNorm(h));  h += MoE(RMSNorm(h))

`Attn_l` is GQA where l is in `gqa_layers` (l % 4 == 0), KDA elsewhere.

GQA  q = W_q u in heads x hd; k, v = W_k u, W_v u in kv_heads x hd; no
     rotary embedding (`use_rope` false); causal softmax(q k^T / sqrt(hd)) v;
     o <- o * sigmoid(W_g u), element-wise (`use_gqa_gate`); W_o.
KDA  q, k, v = SiLU(causal depthwise conv4(W u)); q and k L2-normalised per
     head, q scaled by dk^-1/2; per head and channel
         g_t = -exp(A_log) softplus(W_up W_down u + dt_bias)
     (`kda_use_full_proj` false: the gate's projection is low rank);
     beta_t = 2 sigmoid(W_beta u) per head (`kda_allow_neg_eigval`);
     the recurrence of `ops/kda.py`; out = W_o (RMSNorm_head(o_t) *
     sigmoid(W_g,up W_g,down u)).
MoE  `models/ling.py`'s router and held experts (`route`, `moe_held`,
     `_grouped_experts`, `balance_expert_bias`), used and not copied: one
     group of all the experts, which is no grouping; weights s_i / sum of
     the chosen s_j x `routed_scale`; one shared expert added unweighted.

The blocks here are the model's mathematics for one sequence (`forward`), for
the rows of one engine step (a position of every slot beside one chunk of
one sequence that starts from a carried state: `kda_block`, `gqa_project`,
`gqa_output`); `llm/_solar_steps.py` builds
the engine's jitted step from them and owns the caches. The parameters are a
list of per-layer dicts and the steps unroll them, as Ling's do.

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

from ray_tpu.models import ling
from ray_tpu.models.llama import rms_norm
from ray_tpu.ops import kda as kda_ops

NEG_INF = -1e30
GQA_QUERY_BLOCK = 512


@dataclass(frozen=True)
class SolarConfig:
    vocab_size: int = 196608
    dim: int = 4096
    n_layers: int = 48
    # published indices of the layers kept (None: 0..n_layers-1); the index
    # decides the kind of attention: GQA where id % gqa_period == 0
    layer_ids: Optional[Tuple[int, ...]] = None
    gqa_period: int = 4
    n_heads: int = 64              # GQA query heads
    n_kv_heads: int = 8
    head_dim: int = 128            # GQA head width
    kda_heads: int = 64
    kda_head_dim: int = 128        # KDA key and value width per head
    conv_kernel: int = 4
    gate_rank: int = 128           # both low-rank projections of a KDA layer
    beta_scale: float = 2.0        # beta in (0, 2): negative eigenvalues
    norm_eps: float = 1e-5
    moe_ffn_dim: int = 1280        # each routed expert and the shared one
    n_experts: int = 320           # the router's width
    top_k: int = 8
    routed_scale: float = 1.0
    held_start: int = 0            # this chip's experts: [start, start + n)
    n_held: int = 320
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    # the router has no groups: one group of all the experts, always kept,
    # is what `ling.select_experts` reads as that
    n_group = 1
    topk_group = 1

    @classmethod
    def solar_open2(cls, **kw) -> "SolarConfig":
        """Published widths; keyword arguments override any field."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "SolarConfig":
        """Test size: GQA, KDA, KDA, KDA; 16 experts."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=4, n_heads=8, n_kv_heads=2,
            head_dim=16, kda_heads=4, kda_head_dim=16, gate_rank=8,
            moe_ffn_dim=32, n_experts=16, top_k=2, n_held=16,
            max_seq_len=512, dtype=jnp.float32, param_dtype=jnp.float32),
            **kw})

    def kinds(self) -> List[str]:
        """The attention kind of each kept layer."""
        ids = self.layer_ids or tuple(range(self.n_layers))
        assert len(ids) == self.n_layers, "layer_ids must name n_layers layers"
        return ["gqa" if pub % self.gqa_period == 0 else "kda" for pub in ids]

    @property
    def kda_layers(self) -> int:
        return sum(a == "kda" for a in self.kinds())

    @property
    def gqa_layers(self) -> int:
        return sum(a == "gqa" for a in self.kinds())

    @property
    def conv_channels(self) -> int:
        return 3 * self.kda_heads * self.kda_head_dim


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------


def init_params(cfg: SolarConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, N(0, 1/fan_in), drawn as `ling.init_params`
    draws them: the expert bias N(0, 0.02); the decay's `A_log` = log
    U(0.5, 2) per head and `dt_bias` = U(-6, -1) per channel, which spreads
    a channel's memory from a few positions to thousands."""
    pd, D = cfg.param_dtype, cfg.dim
    H, dk, r = cfg.kda_heads, cfg.kda_head_dim, cfg.gate_rank
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    keys = iter(jax.random.split(key, 32 * cfg.n_layers + 8))

    def dense(fan_in, shape, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    def uniform(lo, hi, shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    layers = []
    for attn in cfg.kinds():
        p: Dict[str, Any] = {"ln1": jnp.ones((D,), pd), "ln2": jnp.ones((D,), pd)}
        if attn == "kda":
            p.update(
                wqkv=dense(D, (D, 3 * H * dk)),
                conv=dense(cfg.conv_kernel, (cfg.conv_kernel, 3 * H * dk)),
                wa_down=dense(D, (D, r)), wa_up=dense(r, (r, H * dk)),
                wbeta=dense(D, (D, H)),
                wg_down=dense(D, (D, r)), wg_up=dense(r, (r, H * dk)),
                A_log=jnp.log(uniform(0.5, 2.0, (H,))),
                dt_bias=uniform(-6.0, -1.0, (H * dk,)),
                o_norm=jnp.ones((dk,), pd), wo=dense(H * dk, (H * dk, D)))
        else:
            # columns [q | k | v]: one matmul a layer, as Llama's step packs
            p.update(wqkv=dense(D, (D, nq + 2 * nkv)), wg=dense(D, (D, nq)),
                     wo=dense(nq, (nq, D)))
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


def seeded_params(cfg: SolarConfig, key: jax.Array) -> Dict[str, Any]:
    """What a server without a checkpoint serves (`llm.MODEL_FAMILIES`):
    `init_params`, then every expert layer's bias balanced as training
    leaves it (`ling.balance_expert_bias`, walking this family's layers)."""
    k_init, k_balance = jax.random.split(key)
    return ling.balance_expert_bias(
        cfg, init_params(cfg, k_init), k_balance,
        attention=functools.partial(attention, cfg))


# ---------------------------------------------------------------------------
# KDA
# ---------------------------------------------------------------------------


def kda_inputs(cfg: SolarConfig, p, x, qkv):
    """From the normed input x [N, D] and the convolved, activated qkv [N,
    3*H*dk] (float32): q, k, v, g [N, H, dk] and beta [N, H], float32."""
    N, H, dk = x.shape[0], cfg.kda_heads, cfg.kda_head_dim
    dt = cfg.dtype
    q, k, v = (t.reshape(N, H, dk) for t in jnp.split(qkv, 3, axis=-1))
    q, k = ling._l2(q) * dk ** -0.5, ling._l2(k)
    a = ((x @ p["wa_down"].astype(dt)) @ p["wa_up"].astype(dt)).astype(
        jnp.float32) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[None, :, None] * jax.nn.softplus(
        a.reshape(N, H, dk))
    beta = cfg.beta_scale * jax.nn.sigmoid(
        (x @ p["wbeta"].astype(dt)).astype(jnp.float32))
    return q, k, v, g, beta


def kda_output(cfg: SolarConfig, p, x, o):
    """o [N, H, dv] float32 -> [N, D]: norm per head, the low-rank gate per
    channel, W_o."""
    dt = cfg.dtype
    o = rms_norm(o, p["o_norm"], cfg.norm_eps).astype(jnp.float32)
    gate = jax.nn.sigmoid(((x @ p["wg_down"].astype(dt))
                           @ p["wg_up"].astype(dt)).astype(jnp.float32))
    o = (o * gate.reshape(o.shape)).astype(dt)
    return o.reshape(o.shape[0], -1) @ p["wo"].astype(dt)


def kda_block(cfg: SolarConfig, p, x, state, tail, chunk=None):
    """A KDA layer on the rows of one engine step: x [B + C, D] normed, one
    position of each of B slots, then the C rows of one chunk of one
    sequence (C = 0 without `chunk`), through the layer's matmuls as one
    batch. state [B, H, dk, dv] float32 and tail [B, K-1, 3*H*dk] are the
    slots'; `chunk` = (n, state [H, dk, dv], tail [K-1, 3*H*dk]): the first
    n of the C rows are real (padding trails) and start from that state and
    tail (what the chunk before left; zeros at position 0).

    Returns (y [B + C, D], the slots' state and tail after their rows (the
    caller keeps an idle slot's as they were), the chunk's state and tail
    after its row n - 1 (None, None without one), the recurrence's inputs
    (q, k, v, g, beta) of all the rows, for a check to replay)."""
    with jax.named_scope("kda"):
        B, K = state.shape[0], cfg.conv_kernel
        pre = x @ p["wqkv"].astype(cfg.dtype)                 # [B + C, ch]
        conv = p["conv"].astype(jnp.float32)
        window = jnp.concatenate(
            [tail, pre[:B, None].astype(tail.dtype)], axis=1)
        qkv = jax.nn.silu(jnp.sum(
            window.astype(jnp.float32) * conv[None], axis=1))
        if chunk is not None:
            n, state_c, tail_c = chunk
            qkv = jnp.concatenate([qkv, kda_ops.causal_conv_silu(
                pre[B:], tail_c, p["conv"])])
        q, k, v, g, beta = kda_inputs(cfg, p, x, qkv)
        o, new = kda_ops.kda_step(q[:B], k[:B], v[:B], g[:B], beta[:B], state)
        if chunk is not None:
            valid = jnp.arange(x.shape[0] - B) < n
            # a padded row leaves the state as it was
            o_c, state_c = kda_ops.kda_chunked(
                q[B:], k[B:], v[B:], jnp.where(valid[:, None, None], g[B:], 0.0),
                jnp.where(valid[:, None], beta[B:], 0.0), state_c)
            o = jnp.concatenate([o, o_c])
            # the K-1 inputs before row n: the old tail's where n < K-1
            before = jnp.concatenate([tail_c.astype(pre.dtype), pre[B:]])
            tail_c = lax.dynamic_slice_in_dim(before, n, K - 1, axis=0)
        else:
            state_c = tail_c = None
        return (kda_output(cfg, p, x, o), new, window[:, 1:], state_c, tail_c,
                (q, k, v, g, beta))


def kda_sequence(cfg: SolarConfig, p, x, valid, state=None, tail=None):
    """One sequence (or a piece of one, from `state` and `tail`): x [T, D]
    normed, valid [T] (padding trails). Returns (y, state, tail)."""
    H, dk, ch = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_channels
    if state is None:
        state = jnp.zeros((H, dk, dk), jnp.float32)
        tail = jnp.zeros((cfg.conv_kernel - 1, ch), cfg.dtype)
    y, _, _, state, tail, _ = kda_block(
        cfg, p, x, jnp.zeros((0, H, dk, dk), jnp.float32),
        jnp.zeros((0, cfg.conv_kernel - 1, ch), tail.dtype),
        (jnp.sum(valid.astype(jnp.int32)), state, tail))
    return y, state, tail


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_project(cfg: SolarConfig, p, x):
    """x [N, D] normed -> q [N, heads, hd], k, v [N, kv_heads, hd] (dtype):
    one matmul over the packed leaf, cut into heads after it."""
    N, hd = x.shape[0], cfg.head_dim
    nq, nkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    qkv = x @ p["wqkv"].astype(cfg.dtype)
    return (qkv[:, :nq].reshape(N, cfg.n_heads, hd),
            qkv[:, nq:nq + nkv].reshape(N, cfg.n_kv_heads, hd),
            qkv[:, nq + nkv:].reshape(N, cfg.n_kv_heads, hd))


def gqa_output(cfg: SolarConfig, p, x, o):
    """o [N, heads, hd] -> [N, D]: the element-wise gate, then W_o."""
    dt = cfg.dtype
    gate = jax.nn.sigmoid((x @ p["wg"].astype(dt)).astype(jnp.float32))
    o = (o.reshape(o.shape[0], -1).astype(jnp.float32) * gate).astype(dt)
    return o @ p["wo"].astype(dt)


def gqa_sequence(cfg: SolarConfig, p, x, valid):
    """One sequence from position 0, no cache. x [T, D]; valid [T]."""
    with jax.named_scope("gqa"):
        T, hd = x.shape[0], cfg.head_dim
        rep = cfg.n_heads // cfg.n_kv_heads
        q, k, v = gqa_project(cfg, p, x)
        pos = jnp.arange(T)
        qb = min(GQA_QUERY_BLOCK, T)
        assert T % qb == 0, "sequence lengths are multiples of the query block"
        qg = q.reshape(T // qb, qb, cfg.n_kv_heads, rep, hd)

        def block(args):
            qi, qpos = args
            s = jnp.einsum("qkrd,wkd->krqw", qi, k,
                           preferred_element_type=jnp.float32) / math.sqrt(hd)
            seen = (pos[None, :] <= qpos[:, None]) & valid[None, :]
            s = jnp.where(seen, s, NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
            return jnp.einsum("krqw,wkd->qkrd", probs, v)

        o = lax.map(block, (qg, pos.reshape(T // qb, qb)))
        return gqa_output(cfg, p, x, o.reshape(T, cfg.n_heads, hd))


# ---------------------------------------------------------------------------
# the whole model on one sequence
# ---------------------------------------------------------------------------


def attention(cfg: SolarConfig, p, x, valid):
    """A layer's attention on one whole sequence x [T, D] (normed)."""
    if "conv" not in p:
        return gqa_sequence(cfg, p, x, valid)
    return kda_sequence(cfg, p, x, valid)[0]


def forward(cfg: SolarConfig, params, tokens, plen=None):
    """tokens [T] (one sequence; T at most 512 or a multiple of it) ->
    logits [T, V] float32. `plen` (default T) marks the trailing padding."""
    T = tokens.shape[0]
    valid = jnp.arange(T) < (T if plen is None else plen)
    dt = cfg.dtype
    h = params["tok_emb"].astype(dt)[tokens]
    for p in params["layers"]:
        h = h + attention(cfg, p, rms_norm(h, p["ln1"], cfg.norm_eps), valid)
        h = h + ling.moe_held(
            cfg, p, rms_norm(h, p["ln2"], cfg.norm_eps), valid)[0]
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["lm_head"].astype(dt)).astype(jnp.float32)

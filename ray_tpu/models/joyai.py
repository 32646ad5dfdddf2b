"""JoyAI-LLM-Flash family (`joyai_llm_flash`): latent attention (MLA) with a
low-rank query in every layer, a dense SwiGLU in the leading layer and
shared + sigmoid-routed experts (ungrouped, 8 of 256) after it.

The latent block, the router and the held-experts layer are `models/ling.py`'s
own, which reads from the layer's parameters and the config what this family
has differently: a query through `wqa`, `q_norm`, `wqb`; no head gate (no
`wg`); the rotary in adjacent pairs (`rope_interleave`); one group
(`n_group` 1). Nothing of them is written again here.

The expert layers are alike, so their parameters are stacked on a leading
axis (`params["layers"]`) and the steps scan them (`llm/_joyai_steps.py`);
the leading dense layers are a list (`params["dense"]`), unrolled.

**Held experts** as in `models/ling.py`: the router keeps its `n_experts`
outputs and top-k; the layer computes what the experts `[held_start,
held_start + n_held)` add for the tokens routed to them, and the shared
expert once.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Iterator

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models import ling
from ray_tpu.models.llama import rms_norm


@dataclass(frozen=True)
class JoyAIConfig:
    vocab_size: int = 129280
    dim: int = 2048
    n_layers: int = 40
    first_k_dense: int = 1         # leading layers with a dense FFN
    n_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32e6
    rope_interleave: bool = True
    norm_eps: float = 1e-6
    ffn_dim: int = 7168            # dense layers
    moe_ffn_dim: int = 768         # each routed expert and the shared one
    n_experts: int = 256           # the router's width
    n_group: int = 1
    topk_group: int = 1
    top_k: int = 8
    routed_scale: float = 2.5
    held_start: int = 0            # this chip's experts: [start, start + n)
    n_held: int = 256
    max_seq_len: int = 16384
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16

    @classmethod
    def joyai_llm_flash(cls, **kw) -> "JoyAIConfig":
        """Published widths; keyword arguments override any field."""
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "JoyAIConfig":
        """Test size: one dense layer, three expert layers, 16 experts."""
        return cls(**{**dict(
            vocab_size=512, dim=64, n_layers=4, n_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16,
            rope_theta=1e4, ffn_dim=128, moe_ffn_dim=32, n_experts=16,
            top_k=2, n_held=16, max_seq_len=512, dtype=jnp.float32,
            param_dtype=jnp.float32), **kw})

    @property
    def moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        """A cached latent's width in memory (`LingConfig.latent_width`:
        576 -> 640, whole lanes)."""
        return -(-self.latent_dim // 128) * 128


def init_params(cfg: JoyAIConfig, key: jax.Array) -> Dict[str, Any]:
    """Seeded random weights, N(0, 1/fan_in); the expert bias N(0, 0.02);
    norm weights ones. The expert layers' leaves carry a leading axis of
    `moe_layers`."""
    pd, D, H = cfg.param_dtype, cfg.dim, cfg.n_heads
    keys = iter(jax.random.split(key, 64))

    def dense(fan_in, shape, dtype=pd):
        return (jax.random.normal(next(keys), shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    def attention(lead):
        qk = cfg.qk_nope_dim + cfg.qk_rope_dim
        return dict(
            ln1=jnp.ones(lead + (D,), pd), ln2=jnp.ones(lead + (D,), pd),
            wqa=dense(D, lead + (D, cfg.q_lora_rank)),
            q_norm=jnp.ones(lead + (cfg.q_lora_rank,), pd),
            wqb=dense(cfg.q_lora_rank, lead + (cfg.q_lora_rank, H * qk)),
            wkva=dense(D, lead + (D, cfg.latent_dim)),
            kv_norm=jnp.ones(lead + (cfg.kv_lora_rank,), pd),
            wkvb=dense(cfg.kv_lora_rank, lead + (
                cfg.kv_lora_rank, H * (cfg.qk_nope_dim + cfg.v_head_dim))),
            wo=dense(H * cfg.v_head_dim, lead + (H * cfg.v_head_dim, D)))

    F = cfg.ffn_dim
    first = [dict(attention(()), w1=dense(D, (D, F)), w3=dense(D, (D, F)),
                  w2=dense(F, (F, D))) for _ in range(cfg.first_k_dense)]
    L, F, n = (cfg.moe_layers,), cfg.moe_ffn_dim, cfg.n_held
    layers = dict(
        attention(L),
        router=dense(D, L + (D, cfg.n_experts), jnp.float32),
        router_bias=0.02 * jax.random.normal(
            next(keys), L + (cfg.n_experts,), jnp.float32),
        sh_w1=dense(D, L + (D, F)), sh_w3=dense(D, L + (D, F)),
        sh_w2=dense(F, L + (F, D)),
        e_w1=dense(D, L + (n, D, F)), e_w3=dense(D, L + (n, D, F)),
        e_w2=dense(F, L + (n, F, D)))
    return {"tok_emb": dense(D, (cfg.vocab_size, D)), "dense": first,
            "layers": layers, "norm": jnp.ones((D,), pd),
            "lm_head": dense(D, (D, cfg.vocab_size))}


def layer_params(params, i: int):
    """Layer i's parameters as `models/ling.py`'s blocks take them."""
    n = len(params["dense"])
    if i < n:
        return params["dense"][i]
    return jax.tree.map(lambda a: a[i - n], params["layers"])


def each_layer(cfg: JoyAIConfig, params) -> Iterator[Dict[str, Any]]:
    return (layer_params(params, i) for i in range(cfg.n_layers))


def _whole_layer(cfg: JoyAIConfig, p, h, valid):
    """A layer on one whole sequence h [T, D] from position 0."""
    h = h + ling.mla_prefill(cfg, p, rms_norm(h, p["ln1"], cfg.norm_eps),
                             valid)[0]
    x = rms_norm(h, p["ln2"], cfg.norm_eps)
    return h, x


def seeded_params(cfg: JoyAIConfig, key: jax.Array) -> Dict[str, Any]:
    """What a server without a checkpoint serves (`llm.MODEL_FAMILIES`):
    `init_params`, then every expert layer's bias balanced as training leaves
    it (`ling.balanced_bias`, for `ling.balance_expert_bias`'s reason), layer
    after layer on the same seeded tokens."""
    k_init, k_balance = jax.random.split(key)
    params = init_params(cfg, k_init)
    live = jnp.ones((ling.BALANCE_TOKENS,), bool)
    h = params["tok_emb"].astype(cfg.dtype)[ling.balance_tokens(k_balance)]
    for p in params["dense"]:
        h, x = _whole_layer(cfg, p, h, live)
        h = h + ling.ffn(cfg, p, x, live)[0]

    def layer(h, p):
        h, x = _whole_layer(cfg, p, h, live)
        bias = ling.balanced_bias(cfg, p, x)
        h = h + ling.ffn(cfg, {**p, "router_bias": bias}, x, live)[0]
        return h, bias

    _, biases = lax.scan(layer, h, params["layers"])
    return {**params, "layers": {**params["layers"], "router_bias": biases}}


def forward(cfg: JoyAIConfig, params, tokens, plen=None):
    """tokens [T] (one sequence; T a power of two, or at most
    `ling.MLA_QUERY_BLOCK`) -> logits [T, V] float32, expanded attention from
    position 0. `plen` (default T) marks the trailing padding."""
    T = tokens.shape[0]
    valid = jnp.arange(T) < (T if plen is None else plen)
    h = params["tok_emb"].astype(cfg.dtype)[tokens]
    for p in each_layer(cfg, params):
        h, x = _whole_layer(cfg, p, h, valid)
        h = h + ling.ffn(cfg, p, x, valid)[0]
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    return (h @ params["lm_head"].astype(cfg.dtype)).astype(jnp.float32)

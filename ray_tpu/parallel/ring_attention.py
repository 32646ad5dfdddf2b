"""Ring attention — sequence/context parallelism over the ICI ring.

Absent from the reference (SURVEY §2b: Ray delegates SP/CP to DeepSpeed/vLLM);
here it is native. The sequence axis is sharded over the mesh's "sp" axis;
each step every device computes blockwise attention of its local queries
against the resident K/V block with an online-softmax accumulator
(flash-attention style: running max, running denominator), then rotates K/V to
its ring neighbor with `lax.ppermute` — on TPU the permute rides neighboring
ICI links, and XLA overlaps the collective with the block compute. Peak memory
is O(seq/sp_size) per device, which is what makes million-token contexts fit.

Each hop is classified by ring offset:
  * FULL — the K/V block is entirely in this shard's causal past: the hop
    runs the Pallas flash-chunk kernel unmasked (ops.flash_attention
    .flash_chunk_bhsd — no (sq, sk) score materialization on TPU);
  * DIAG — the resident block: the kernel runs with the local causal mask;
  * SKIP — entirely in the future: the hop is skipped outright (no FLOPs,
    forward or backward), which halves causal ring-attention work vs.
    computing fully-masked blocks.

Differentiation is a RING-LEVEL custom VJP: the forward saves only
(q, k, v, out, lse) per shard — O(s·d), never the O((s/sp)²) score blocks —
and the backward runs a second ring pass in which dk/dv accumulators rotate
together with their K/V blocks, each hop computed by the Pallas dq/dkv
kernels against the globally-saved lse/delta rows
(ops.flash_attention.flash_hop_bwd). No (sq, sk) tensor exists in either
direction on TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.mesh import BATCH_AXES


def _ring_perm(sp_size):
    return [(j, (j + 1) % sp_size) for j in range(sp_size)]


def _dispatch_hop(causal, idx, i, sp_size, hop_full, hop_diag, hop_skip,
                  args):
    """The correctness-critical hop classification, shared by forward and
    backward: 0 = FULL (K/V block in this shard's causal past), 1 = DIAG
    (resident block, local causal mask), 2 = SKIP (future block — no work)."""
    src = (idx - i) % sp_size  # ring position this K/V block came from
    if not causal:
        return hop_full(args)
    branch = jnp.int32(2) - (src <= idx) - (src < idx)
    return lax.switch(branch, (hop_full, hop_diag, hop_skip), args)


def _ring_fwd_impl(q, k, v, static):
    """Forward ring loop. q: (b, h, sq, hd); k/v: (b, kvh, sk, hd) local
    shards inside shard_map. Returns (out, lse)."""
    from ray_tpu.ops.flash_attention import flash_chunk_bhsd

    sp_size, causal = static
    idx = lax.axis_index("sp")
    b, h, sq, hd = q.shape
    out_dtype = q.dtype

    o = jnp.zeros((b, h, sq, hd), jnp.float32)
    m = jnp.full((b, h, sq, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq, 1), jnp.float32)
    perm = _ring_perm(sp_size)

    def hop_full(args):
        o, m, l, k, v = args
        return flash_chunk_bhsd(q, k, v, o, m, l, False)

    def hop_diag(args):
        o, m, l, k, v = args
        return flash_chunk_bhsd(q, k, v, o, m, l, True)

    def hop_skip(args):
        o, m, l, _, _ = args
        return o, m, l

    def step(i, carry):
        o, m, l, k, v = carry
        o, m, l = _dispatch_hop(causal, idx, i, sp_size,
                                hop_full, hop_diag, hop_skip, (o, m, l, k, v))
        # rotate K/V around the ring (skipped after the final block)
        k, v = lax.cond(
            i < sp_size - 1,
            lambda kv: (
                lax.ppermute(kv[0], "sp", perm),
                lax.ppermute(kv[1], "sp", perm),
            ),
            lambda kv: kv,
            (k, v),
        )
        return o, m, l, k, v

    o, m, l, _, _ = lax.fori_loop(0, sp_size, step, (o, m, l, k, v))
    # under causal the diagonal always contributes, so l > 0 on every row
    out = (o / l).astype(out_dtype)
    lse = m + jnp.log(l)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ring_core(q, k, v, static):
    out, _ = _ring_fwd_impl(q, k, v, static)
    return out


def _ring_core_fwd(q, k, v, static):
    out, lse = _ring_fwd_impl(q, k, v, static)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(static, res, g):
    """Second ring pass: dk/dv accumulators travel WITH their K/V blocks
    (rotated every step, so after sp_size hops each block's gradient lands
    back on its home shard); dq accumulates locally."""
    from ray_tpu.ops.flash_attention import flash_hop_bwd

    sp_size, causal = static
    q, k, v, out, lse = res
    idx = lax.axis_index("sp")
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    perm = _ring_perm(sp_size)

    def hop(causal_flag):
        def run(args):
            dq, dk, dv, k, v = args
            dq_p, dk_p, dv_p = flash_hop_bwd(
                q, k, v, g, lse, delta, causal_flag)
            return dq + dq_p, dk + dk_p, dv + dv_p
        return run

    hop_full, hop_diag = hop(False), hop(True)

    def hop_skip(args):
        dq, dk, dv, _, _ = args
        return dq, dk, dv

    def step(i, carry):
        dq, dk, dv, k, v = carry
        dq, dk, dv = _dispatch_hop(causal, idx, i, sp_size,
                                   hop_full, hop_diag, hop_skip,
                                   (dq, dk, dv, k, v))
        # dk/dv rotate every step (including the last — after sp_size
        # rotations each block's gradient is home again); k/v are never
        # read after the final hop, so their last rotation is skipped
        dk = lax.ppermute(dk, "sp", perm)
        dv = lax.ppermute(dv, "sp", perm)
        k, v = lax.cond(
            i < sp_size - 1,
            lambda kv: (
                lax.ppermute(kv[0], "sp", perm),
                lax.ppermute(kv[1], "sp", perm),
            ),
            lambda kv: kv,
            (k, v),
        )
        return dq, dk, dv, k, v

    dq, dk, dv, _, _ = lax.fori_loop(
        0, sp_size, step, (dq0, dk0, dv0, k, v))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, causal: bool = True
) -> jax.Array:
    """Causal attention with seq sharded over the "sp" mesh axis.

    q/k/v: (batch, seq, heads, head_dim) GLOBAL shapes; seq is sharded.
    Returns same shape/dtype as q.
    """
    spec = P(BATCH_AXES, "sp", None, None)
    sp_size = mesh.shape["sp"]
    static = (sp_size, causal)

    def local_fn(q, k, v):
        # bhsd layout into the kernels: head_dim rides the lane dimension
        out = _ring_core(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), static)
        return out.transpose(0, 2, 1, 3)

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
        # the Pallas hop kernels' bodies (loop carries that mix ref loads
        # and constants) do not type under the varying-axis checker
        check_vma=False,
    )(q, k, v)


def ring_attention_reference(q, k, v, causal: bool = True):
    """Single-device reference for testing numerical parity."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(q.dtype)

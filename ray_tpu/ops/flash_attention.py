"""Flash attention (forward + backward) in Pallas for TPU.

Blockwise online-softmax attention that never materializes the (s, s) score
matrix in either direction:

* forward: for each query block the kernel streams key/value blocks through
  VMEM, keeping fp32 running max/denominator/accumulator in registers, and
  writes out the per-row logsumexp for the backward pass. Causal blocks after
  the diagonal are skipped (work ∝ s²/2).
* backward: two kernels (FlashAttention-2 style). `dq` iterates key blocks for
  each query block; `dk/dv` iterates query blocks for each key block. Both
  recompute p = exp(qkᵀ·scale − lse) from the saved logsumexp — no (s, s)
  residual is ever stored, which is what lets the surrounding model train
  without global rematerialization.
* residuals: the backward reads five arrays, `q`, `k`, `v` (the caller's
  projections: under a dots-saving `jax.checkpoint` policy they are saved
  matmul outputs), the kernel's output `o` (bf16, q's shape) and its
  logsumexp rows `lse` (float32, (b, h, s)). The last two come out of a
  Pallas call, which is no dot, so a policy keeps them only by name:
  `RESIDUAL_NAMES` (`jax.checkpoint_policies.save_only_these_names`).
  Without the names a checkpointed layer runs the forward kernel a second
  time in its backward pass; outside a `jax.checkpoint` they are the
  identity. `models.llama.make_train_step`'s `remat="dots"` keeps both.

Layout is (batch, heads, seq, head_dim) end-to-end ("bhsd"): head_dim rides
the 128-wide lane dimension and no transposes are introduced around the
kernel. A (batch, seq, heads, head_dim) wrapper is kept for callers that use
the attention-standard layout. GQA is handled in the BlockSpec index maps
(query heads sharing a kv head read the same k/v block).

Off-TPU (CPU tests) the public entry points run a fused XLA implementation
with identical semantics — it is the reference the kernels are checked
against. On a TPU backend they run the compiled kernels or raise naming the
shape constraint that failed; they never quietly become the XLA path.

Reference gap: the reference has no attention kernels at all (delegated to
vLLM/torch — SURVEY §2b); pallas_guide.md is the kernel playbook used here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

_INTERPRET = False  # test-only: run the kernels in the Pallas interpreter

NEG_INF = -1e30


def _out(shape, dtype, *operands):
    """A pallas_call out_shape entry that varies over the manual mesh axes
    its operands vary over. Inside shard_map the type system needs that
    stated (a Mosaic kernel only runs on a multi-chip mesh inside one: GSPMD
    cannot partition it); outside, the set is empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# ---------------------------------------------------------------------------
# XLA reference (the only path off-TPU)
# ---------------------------------------------------------------------------


def _xla_attention_bhsd(q, k, v, causal: bool):
    """q: (b, h, s, hd); k/v: (b, kvh, s, hd) → (b, h, s, hd)."""
    b, h, sq, hd = q.shape
    kvh = k.shape[1]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sk = k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v).astype(q.dtype)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _kv_streamer(stream, block_k, bi, kh, k_src, v_src, scratch):
    """Returns (warmup, prefetch, load) for the per-iteration K/V tiles.

    stream=False: k_src/v_src are whole-s VMEM refs — direct slices, the
    BlockSpec auto-pipeline overlaps the HBM traffic. stream=True:
    k_src/v_src stay in HBM and tiles move through double-buffered VMEM
    scratch — O(block) VMEM at any seq_len."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not stream:
        def load(j, _slot):
            kb = k_src[0, 0, pl.ds(j * block_k, block_k), :]
            vb = v_src[0, 0, pl.ds(j * block_k, block_k), :]
            return kb.astype(jnp.float32), vb.astype(jnp.float32)

        return (lambda: None), (lambda j, limit: None), load

    k_buf, v_buf, k_sem, v_sem = scratch

    def dma(buf, hbm, sem, slot, j):
        return pltpu.make_async_copy(
            hbm.at[bi, kh, pl.ds(j * block_k, block_k), :],
            buf.at[slot], sem.at[slot])

    def warmup():
        dma(k_buf, k_src, k_sem, 0, 0).start()
        dma(v_buf, v_src, v_sem, 0, 0).start()

    def prefetch(j, limit):
        @pl.when(j + 1 < limit)
        def _():
            nxt = jax.lax.rem(j + 1, 2)
            dma(k_buf, k_src, k_sem, nxt, j + 1).start()
            dma(v_buf, v_src, v_sem, nxt, j + 1).start()

    def load(j, slot):
        dma(k_buf, k_src, k_sem, slot, j).wait()
        dma(v_buf, v_src, v_sem, slot, j).wait()
        return k_buf[slot].astype(jnp.float32), v_buf[slot].astype(jnp.float32)

    return warmup, prefetch, load


def _fwd_kernel(q_ref, k_src, v_src, o_ref, lse_ref, *scratch, causal,
                scale, block_q, block_k, seq_len, rep, stream):
    """Online-softmax forward for one (batch, head, q-block)."""
    from jax.experimental import pallas as pl

    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qb = q_ref[0, 0].astype(jnp.float32) * scale           # (block_q, hd)
    hd = qb.shape[-1]

    num_kb = (
        pl.cdiv(qi * block_q + block_q, block_k) if causal
        else seq_len // block_k
    )
    warmup, prefetch, load = _kv_streamer(
        stream, block_k, bi, hi // rep, k_src, v_src, scratch)
    warmup()

    def body(j, carry):
        o, m, l = carry
        slot = jax.lax.rem(j, 2)
        prefetch(j, num_kb)
        kb, vb = load(j, slot)
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        block_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, block_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        new_o = o * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return new_o, new_m, new_l

    o0 = jnp.zeros((block_q, hd), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o, m, l = lax.fori_loop(0, jnp.asarray(num_kb, jnp.int32), body,
                            (o0, m0, l0))
    o_ref[0, 0] = (o / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)


# Whole-s VMEM refs (the BlockSpec auto-pipeline overlaps grid steps) while
# they fit scoped VMEM, manual streaming above. What the compiler accepts,
# from an ahead-of-time compile for "TPU v5 lite" (libtpu 0.0.34, 512-blocks,
# hd=128, bf16): whole-s fwd compiles at s=8192 and overflows the 16 MB
# scoped limit at 16384 (16.5 MB); whole-s dq+dkv compile at 16384 and
# overflow at 32768. The dkv threshold is therefore conservative; which side
# of either threshold is faster has not been measured.
_STREAM_KV_ELEMS = 8192 * 128    # fwd + dq: stream k/v above this s*hd
_STREAM_QDO_ELEMS = 4096 * 128   # dkv: stream q/do above this s*hd


def _qdo_specs(stream, s, hd, block_q, qdt, gdt):
    """in_specs (q, do) + scratch for the k-gridded dkv kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if stream:
        specs = [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [
            pltpu.VMEM((2, block_q, hd), qdt),
            pltpu.VMEM((2, block_q, hd), gdt),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        return specs, scratch
    specs = [
        pl.BlockSpec((1, 1, s, hd), lambda bi, hi, ki: (bi, hi, 0, 0)),
        pl.BlockSpec((1, 1, s, hd), lambda bi, hi, ki: (bi, hi, 0, 0)),
    ]
    return specs, []


def _kv_specs(stream, s, hd, block_k, kdt, vdt, rep):
    """in_specs + scratch for the k/v pair of a q-gridded kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if stream:
        specs = [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [
            pltpu.VMEM((2, block_k, hd), kdt),
            pltpu.VMEM((2, block_k, hd), vdt),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        return specs, scratch
    specs = [
        pl.BlockSpec((1, 1, s, hd), lambda bi, hi, qi: (bi, hi // rep, 0, 0)),
        pl.BlockSpec((1, 1, s, hd), lambda bi, hi, qi: (bi, hi // rep, 0, 0)),
    ]
    return specs, []


def _flash_fwd_tpu(q, k, v, causal, block_q, block_k):
    """q: (b, h, s, hd); k/v: (b, kvh, s, hd). Returns (o, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, hd = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    scale = 1.0 / math.sqrt(hd)
    grid = (b, h, s // block_q)
    stream = s * hd > _STREAM_KV_ELEMS

    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, seq_len=s, rep=rep, stream=stream)
    kv_specs, kv_scratch = _kv_specs(stream, s, hd, block_k, k.dtype,
                                     v.dtype, rep)

    o, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        out_shape=(
            _out((b, h, s, hd), q.dtype, q, k, v),
            _out((b, h, s, 1), jnp.float32, q, k, v),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            *kv_specs,
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ),
        scratch_shapes=kv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * 2 * b * h * s * s * hd * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=int(b * h * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=_INTERPRET,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_src, v_src, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, causal, scale, block_q, block_k, seq_len, rep,
               stream):
    """dq for one (batch, head, q-block); k/v via _kv_streamer."""
    from jax.experimental import pallas as pl

    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qb = q_ref[0, 0].astype(jnp.float32) * scale            # (block_q, hd)
    dob = do_ref[0, 0].astype(jnp.float32)                  # (block_q, hd)
    lse = lse_ref[0, 0]                                     # (block_q, 1)
    delta = delta_ref[0, 0]                                 # (block_q, 1)
    hd = qb.shape[-1]

    num_kb = (
        pl.cdiv(qi * block_q + block_q, block_k) if causal
        else seq_len // block_k
    )
    warmup, prefetch, load = _kv_streamer(
        stream, block_k, bi, hi // rep, k_src, v_src, scratch)
    warmup()

    def body(j, dq):
        slot = jax.lax.rem(j, 2)
        prefetch(j, num_kb)
        kb, vb = load(j, slot)
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                                 # (block_q, block_k)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, jnp.asarray(num_kb, jnp.int32), body,
                       jnp.zeros((block_q, hd), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_src, k_ref, v_ref, do_src, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch, causal, scale, block_q, block_k,
                seq_len, rep, stream):
    """dk/dv for one (batch, query-head, k-block). stream=True moves q/do
    tiles from HBM through double-buffered VMEM scratch (O(block) VMEM at
    any seq_len); stream=False keeps them whole-s in VMEM. lse/delta always arrive as (b, h, 1, s) LANE-major rows, whole-s
    in VMEM: that layout pads only the sublane dim (8·s·4B, vs 128·s·4B
    for (s, 1) columns); each q-tile's rows are relayouted to a
    (block_q, 1) column in-kernel, which Mosaic supports."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bi, hi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kb = k_ref[0, 0].astype(jnp.float32)                    # (block_k, hd)
    vb = v_ref[0, 0].astype(jnp.float32)                    # (block_k, hd)
    hd = kb.shape[-1]

    num_qb = seq_len // block_q
    # causal: only query blocks at/after this key block contribute
    start_qb = (ki * block_k) // block_q if causal else 0

    if stream:
        q_buf, do_buf, q_sem, do_sem = scratch

        def dma_rows(buf, hbm, sem, slot, i):
            return pltpu.make_async_copy(
                hbm.at[bi, hi, pl.ds(i * block_q, block_q), :],
                buf.at[slot], sem.at[slot])

        def start_all(slot, i):
            dma_rows(q_buf, q_src, q_sem, slot, i).start()
            dma_rows(do_buf, do_src, do_sem, slot, i).start()

        def prefetch(i):
            @pl.when(i + 1 < num_qb)
            def _():
                start_all(jax.lax.rem(i + 1, 2), i + 1)

        def load_rows(i, slot):
            dma_rows(q_buf, q_src, q_sem, slot, i).wait()
            dma_rows(do_buf, do_src, do_sem, slot, i).wait()
            return (q_buf[slot].astype(jnp.float32),
                    do_buf[slot].astype(jnp.float32))

        start_all(jax.lax.rem(jnp.asarray(start_qb, jnp.int32), 2),
                  jnp.asarray(start_qb, jnp.int32))
    else:
        def prefetch(i):
            pass

        def load_rows(i, _slot):
            qb = q_src[0, 0, pl.ds(i * block_q, block_q), :]
            dob = do_src[0, 0, pl.ds(i * block_q, block_q), :]
            return qb.astype(jnp.float32), dob.astype(jnp.float32)

    def body(i, carry):
        dk, dv = carry
        slot = jax.lax.rem(i, 2)
        prefetch(i)
        qb, dob = load_rows(i, slot)
        lse = lse_ref[0, 0, 0, pl.ds(i * block_q, block_q)][:, None]
        delta = delta_ref[0, 0, 0, pl.ds(i * block_q, block_q)][:, None]
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                                 # (block_q, block_k)
        # dv += pᵀ @ dO
        dv = dv + lax.dot_general(p, dob, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dk += dsᵀ @ q
        dk = dk + lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((block_k, hd), jnp.float32)
    dk, dv = lax.fori_loop(jnp.asarray(start_qb, jnp.int32),
                           jnp.asarray(num_qb, jnp.int32), body, (z, z))
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd_tpu(q, k, v, o, lse, g, causal, block_q, block_k,
                   dkv_block_q=None, dkv_block_k=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, hd = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    scale = 1.0 / math.sqrt(hd)
    dkv_block_q = dkv_block_q or block_q
    dkv_block_k = dkv_block_k or block_k

    # delta[i] = Σ_d dO[i,d]·O[i,d] — cheap rowwise reduce, fused by XLA
    delta = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)

    kv_stream = s * hd > _STREAM_KV_ELEMS
    dq_kernel = functools.partial(
        _dq_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, seq_len=s, rep=rep,
        stream=kv_stream)
    kv_specs, kv_scratch = _kv_specs(kv_stream, s, hd, block_k, k.dtype,
                                     v.dtype, rep)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_dq",
        out_shape=_out((b, h, s, hd), q.dtype, q, k, v, g),
        grid=(b, h, s // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            *kv_specs,
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        scratch_shapes=kv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(3 * 2 * b * h * s * s * hd * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size * 3) * q.dtype.itemsize,
            transcendentals=int(b * h * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=_INTERPRET,
    )(q, k, v, g, lse, delta)

    # dk/dv per *query* head (grid over h), reduced over the GQA group after.
    qdo_stream = s * hd > _STREAM_QDO_ELEMS
    dkv_kernel = functools.partial(
        _dkv_kernel, causal=causal, scale=scale,
        block_q=dkv_block_q, block_k=dkv_block_k, seq_len=s, rep=rep,
        stream=qdo_stream)
    qdo_specs, qdo_scratch = _qdo_specs(qdo_stream, s, hd, dkv_block_q,
                                        q.dtype, g.dtype)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_dkv",
        out_shape=(
            _out((b, h, s, hd), jnp.float32, q, k, v, g),
            _out((b, h, s, hd), jnp.float32, q, k, v, g),
        ),
        grid=(b, h, s // dkv_block_k),
        in_specs=[
            qdo_specs[0],
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi // rep, ki, 0)),
            qdo_specs[1],
            pl.BlockSpec((1, 1, 1, s), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, s), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ),
        scratch_shapes=qdo_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * 2 * b * h * s * s * hd * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size * 4) * q.dtype.itemsize,
            transcendentals=int(b * h * s * s * (0.5 if causal else 1.0)),
        ),
        interpret=_INTERPRET,
    )(q, k, v, g, lse.reshape(b, h, 1, s), delta.reshape(b, h, 1, s))

    if rep != 1:
        dk = dk.reshape(b, kvh, rep, s, hd).sum(axis=2)
        dv = dv.reshape(b, kvh, rep, s, hd).sum(axis=2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# accumulator-carrying chunk attention (the ring-attention hop primitive)
# ---------------------------------------------------------------------------


def _chunk_xla(q, k, v, o, m, l, causal):
    """Online-softmax accumulation of one K/V chunk, XLA reference.

    q: (b, h, sq, hd); k/v: (b, kvh, sk, hd); o: (b, h, sq, hd) fp32;
    m/l: (b, h, sq, 1) fp32 running max / denominator.
    `causal` masks with LOCAL positions (the diagonal ring hop, sq == sk);
    off-diagonal hops are either fully unmasked or skipped by the caller.
    """
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    if kvh != h:
        rep = h // kvh
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        logits = jnp.where((q_pos >= k_pos)[None, None], logits, NEG_INF)
    block_max = jnp.max(logits, axis=-1, keepdims=True)
    new_m = jnp.maximum(m, block_max)
    corr = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m)
    new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    new_o = o * corr + pv
    return new_o, new_m, new_l


def _chunk_kernel(q_ref, k_src, v_src, oi_ref, mi_ref, li_ref,
                  oo_ref, mo_ref, lo_ref, *scratch,
                  causal, scale, block_q, block_k, sk, rep, stream):
    from jax.experimental import pallas as pl

    bi, hi, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    qb = q_ref[0, 0].astype(jnp.float32) * scale           # (block_q, hd)

    num_kb = (
        pl.cdiv(qi * block_q + block_q, block_k) if causal
        else sk // block_k
    )
    warmup, prefetch, load = _kv_streamer(
        stream, block_k, bi, hi // rep, k_src, v_src, scratch)
    warmup()

    def body(j, carry):
        o, m, l = carry
        slot = jax.lax.rem(j, 2)
        prefetch(j, num_kb)
        kb, vb = load(j, slot)
        s = lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        block_max = jnp.max(s, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, block_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(s - new_m)
        new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        new_o = o * corr + lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return new_o, new_m, new_l

    o, m, l = lax.fori_loop(
        0, jnp.asarray(num_kb, jnp.int32), body,
        (oi_ref[0, 0], mi_ref[0, 0], li_ref[0, 0]))
    oo_ref[0, 0] = o
    mo_ref[0, 0] = m
    lo_ref[0, 0] = l


def _flash_chunk_tpu(q, k, v, o, m, l, causal, block_q, block_k):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = 1.0 / math.sqrt(hd)

    stream = sk * hd > _STREAM_KV_ELEMS
    kernel = functools.partial(
        _chunk_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, sk=sk, rep=rep, stream=stream)
    kv_specs, kv_scratch = _kv_specs(stream, sk, hd, block_k, k.dtype,
                                     v.dtype, rep)
    return pl.pallas_call(
        kernel,
        name="flash_chunk",
        out_shape=(
            _out((b, h, sq, hd), jnp.float32, q, k, v, o, m, l),
            _out((b, h, sq, 1), jnp.float32, q, k, v, o, m, l),
            _out((b, h, sq, 1), jnp.float32, q, k, v, o, m, l),
        ),
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            *kv_specs,
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ),
        scratch_shapes=kv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * 2 * b * h * sq * sk * hd * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size + k.size + v.size + o.size)
            * q.dtype.itemsize,
            transcendentals=int(b * h * sq * sk * (0.5 if causal else 1.0)),
        ),
        interpret=_INTERPRET,
    )(q, k, v, o, m, l)


def _kernel_path(what, q, k, block_q, block_k) -> bool:
    """Whether `what` runs its Pallas kernel on q (b, h, sq, hd) against
    k (b, kvh, sk, hd). Off-TPU: False, the XLA reference runs (unless a
    test switched the interpreter on). On a TPU backend: True, or ValueError
    naming every violated constraint — an unsupported shape must not
    quietly become the XLA path there."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return False
    (h, sq, hd), (kvh, sk) = q.shape[1:], k.shape[1:3]
    need = []
    if hd % 128:
        need.append(f"head_dim % 128 == 0 (head_dim={hd})")
    if h % kvh:
        need.append(f"heads % kv_heads == 0 ({h} % {kvh})")
    if sq % block_q:
        need.append(f"q_len % block_q == 0 ({sq} % {block_q})")
    if sk % block_k:
        need.append(f"kv_len % block_k == 0 ({sk} % {block_k})")
    if need:
        raise ValueError(
            f"{what}: no Pallas kernel for this shape on TPU; it needs "
            + "; ".join(need))
    return True


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def flash_chunk_bhsd(q, k, v, o, m, l, causal=False,
                     block_q: int = 512, block_k: int = 512):
    """One online-softmax accumulation hop with carried (o, m, l) state.

    The ring-attention primitive: forward runs the Pallas kernel (no (sq, sk)
    materialization); backward recomputes the hop in XLA — with custom_vjp
    the residuals are just the six inputs, so ring attention training stores
    O(s·d) per hop instead of the O(s²/sp) probability blocks JAX autodiff
    would save.
    """
    bq = min(block_q, q.shape[2])
    bk = min(block_k, k.shape[2])
    if _kernel_path("flash_chunk_bhsd", q, k, bq, bk):
        return _flash_chunk_tpu(q, k, v, o, m, l, causal, bq, bk)
    return _chunk_xla(q, k, v, o, m, l, causal)


def _chunk_fwd_rule(q, k, v, o, m, l, causal, block_q, block_k):
    out = flash_chunk_bhsd(q, k, v, o, m, l, causal, block_q, block_k)
    return out, (q, k, v, o, m, l)


def _chunk_bwd_rule(causal, block_q, block_k, res, g):
    q, k, v, o, m, l = res
    _, vjp = jax.vjp(
        lambda q, k, v, o, m, l: _chunk_xla(q, k, v, o, m, l, causal),
        q, k, v, o, m, l)
    return vjp(g)


flash_chunk_bhsd.defvjp(_chunk_fwd_rule, _chunk_bwd_rule)


# ---------------------------------------------------------------------------
# ring hop backward (used by ring attention's ring-level custom VJP)
# ---------------------------------------------------------------------------


def _hop_bwd_xla(q, k, v, g, lse, delta, causal):
    """FA2-style backward for one ring hop, XLA fallback.

    q/g: (b, h, sq, hd); k/v: (b, kvh, sk, hd); lse/delta: (b, h, sq, 1)
    fp32 — the GLOBAL logsumexp / dO·O row sums saved by the ring forward.
    Returns (dq, dk, dv) in fp32 with dk/dv at kvh heads.
    """
    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    rep = h // kvh
    kr, vr = k, v
    if rep != 1:
        kr = jnp.repeat(k, rep, axis=1)
        vr = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kr,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        k_pos = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
    p = jnp.exp(s - lse)
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, vr.astype(jnp.float32))
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kr.astype(jnp.float32)) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32)) * scale
    if rep != 1:
        dk = dk.reshape(b, kvh, rep, sk, hd).sum(axis=2)
        dv = dv.reshape(b, kvh, rep, sk, hd).sum(axis=2)
    return dq, dk, dv


def _hop_bwd_tpu(q, k, v, g, lse, delta, causal, block_q, block_k,
                 dkv_block_q=None, dkv_block_k=None):
    """Pallas hop backward: the dq/dkv kernels against one K/V block with
    externally supplied (global) lse/delta — no (sq, sk) materialization."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, hd = q.shape
    kvh, sk = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = 1.0 / math.sqrt(hd)
    dkv_block_q = dkv_block_q or block_q
    dkv_block_k = dkv_block_k or block_k

    kv_stream = sk * hd > _STREAM_KV_ELEMS
    dq_kernel = functools.partial(
        _dq_kernel, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, seq_len=sk, rep=rep,
        stream=kv_stream)
    kv_specs, kv_scratch = _kv_specs(kv_stream, sk, hd, block_k, k.dtype,
                                     v.dtype, rep)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_hop_dq",
        out_shape=_out((b, h, sq, hd), jnp.float32, q, k, v, g),
        grid=(b, h, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            *kv_specs,
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        scratch_shapes=kv_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(q, k, v, g, lse, delta)

    qdo_stream = sq * hd > _STREAM_QDO_ELEMS
    dkv_kernel = functools.partial(
        _dkv_kernel, causal=causal, scale=scale,
        block_q=dkv_block_q, block_k=dkv_block_k, seq_len=sq, rep=rep,
        stream=qdo_stream)
    qdo_specs, qdo_scratch = _qdo_specs(qdo_stream, sq, hd, dkv_block_q,
                                        q.dtype, g.dtype)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_hop_dkv",
        out_shape=(
            _out((b, h, sk, hd), jnp.float32, q, k, v, g),
            _out((b, h, sk, hd), jnp.float32, q, k, v, g),
        ),
        grid=(b, h, sk // dkv_block_k),
        in_specs=[
            qdo_specs[0],
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi // rep, ki, 0)),
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi // rep, ki, 0)),
            qdo_specs[1],
            pl.BlockSpec((1, 1, 1, sq), lambda bi, hi, ki: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, sq), lambda bi, hi, ki: (bi, hi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, dkv_block_k, hd), lambda bi, hi, ki: (bi, hi, ki, 0)),
        ),
        scratch_shapes=qdo_scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=_INTERPRET,
    )(q, k, v, g, lse.reshape(b, h, 1, sq), delta.reshape(b, h, 1, sq))

    if rep != 1:
        dk = dk.reshape(b, kvh, rep, sk, hd).sum(axis=2)
        dv = dv.reshape(b, kvh, rep, sk, hd).sum(axis=2)
    return dq, dk, dv


def flash_hop_bwd(q, k, v, g, lse, delta, causal,
                  block_q: int = 512, block_k: int = 512):
    """Backward of one ring-attention hop given global lse/delta rows."""
    bq = min(block_q, q.shape[2])
    bk = min(block_k, k.shape[2])
    if _kernel_path("flash_hop_bwd", q, k, bq, bk):
        return _hop_bwd_tpu(q, k, v, g, lse, delta, causal, bq, bk,
                            dkv_block_q=bq, dkv_block_k=bk)
    return _hop_bwd_xla(q, k, v, g, lse, delta, causal)


# ---------------------------------------------------------------------------
# custom-vjp wiring (bhsd core)
# ---------------------------------------------------------------------------


# `jax.ad_checkpoint.checkpoint_name`s of the forward kernel's two results
# as the backward rule reads them: what a `jax.checkpoint` policy must keep
# (`save_only_these_names(*RESIDUAL_NAMES)`) for the backward pass not to
# run `flash_fwd` again.
FLASH_OUT = "flash_attention_out"
FLASH_LSE = "flash_attention_lse"
RESIDUAL_NAMES = (FLASH_OUT, FLASH_LSE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, block_q, block_k, bwd_block_q, bwd_block_k):
    if _kernel_path("flash_attention", q, k, block_q, block_k):
        return _flash_fwd_tpu(q, k, v, causal, block_q, block_k)[0]
    return _xla_attention_bhsd(q, k, v, causal)


def _flash_fwd_rule(q, k, v, causal, block_q, block_k, bwd_block_q,
                    bwd_block_k):
    if _kernel_path("flash_attention", q, k, block_q, block_k):
        o, lse = _flash_fwd_tpu(q, k, v, causal, block_q, block_k)
        # named before they part: the returned `o` and the residual `o` are
        # then one variable, which is the one the backward reads
        o = checkpoint_name(o, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        return o, (q, k, v, o, lse)
    return _xla_attention_bhsd(q, k, v, causal), (q, k, v, None, None)


def _flash_bwd_rule(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                    res, g):
    q, k, v, o, lse = res
    if o is not None:
        # dq runs at the full forward block size; only dkv (which holds
        # full-s q AND do in VMEM) needs the smaller long-context blocks
        return _flash_bwd_tpu(q, k, v, o, lse, g, causal, block_q, block_k,
                              dkv_block_q=bwd_block_q,
                              dkv_block_k=bwd_block_k)
    _, vjp = jax.vjp(
        lambda q, k, v: _xla_attention_bhsd(q, k, v, causal), q, k, v)
    return vjp(g)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def flash_attention_bhsd(q, k, v, causal: bool = True,
                         block_q: int = 512, block_k: int = 512):
    """q: (batch, heads, seq, head_dim); k/v: (batch, kv_heads, seq, head_dim).

    The TPU-native layout: head_dim on the lane dimension, no transposes.
    """
    s = q.shape[2]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if block_k % block_q != 0:
        block_q = block_k = min(block_q, block_k)
    # backward blocks match the forward: the dq/dkv kernels stream their
    # full-sequence operands from HBM through double-buffered tiles, so
    # VMEM use is O(block) at any seq_len (the old whole-s BlockSpecs
    # overflowed scoped VMEM at 8k/16k and forced 256-blocks)
    return _flash_bhsd(q, k, v, causal, block_q, block_k, block_q, block_k)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 512, block_k: int = 512):
    """Layout-standard entry. q/k/v: (batch, seq, heads, head_dim)."""
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    out = flash_attention_bhsd(q, k, v, causal, block_q, block_k)
    return out.transpose(0, 2, 1, 3)

"""Power retention (power 2): attention weights `(q . k)^2` in place of
`exp(q . k)`, a gate on the past, normalised by the sum of the weights,
computed as a recurrence over the symmetric square of the key.

Per KV head, with keys and queries of `d` channels, values of `dv`, a log
gate `gamma_t <= 0` and `G` query heads that read the head (q already carries
the scale `d^-1/2`, so that `(q . k)^2` is the scaled power):

    a_tj = exp(gamma_{j+1} + ... + gamma_t) (q_t . k_j)^2        j <= t
    o_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

`retention_quadratic` is that definition. The recurrence that serves it:

    S_t = e^{gamma_t} S_{t-1} + phi(k_t) v_t^T        [W, dv] float32
    Z_t = e^{gamma_t} Z_{t-1} + k_t k_t^T             [d, d]  float32
    o_t = S_t^T phi(q_t) / (q_t^T Z_t q_t + eps)

**The layout of phi** (`phi`, `phi_width`, `unpack_state`). `phi(x)` holds
`x_a x_b` once for every unordered pair, so that `phi(q) . phi(k) =
(q . k)^2` exactly, in blocks of 8 channels: block I (a in [8I, 8I + 8)) holds
the products with every b in [8I, d), b-major: feature `off_I + (b - 8I) * 8
+ (a - 8I)`, with `off_I = 8 * sum_{I' < I} (d - 8I')`. Inside the diagonal
block (b < 8I + 8) both orders of a pair are kept, each with weight 1; past it
one order is kept with weight sqrt 2 on either side. The width is `W = 8 *
sum_I (d - 8I) = d^2 / 2 + 4 d`: 8,704 at d = 128, where the plain
symmetric square has 8,256 (5% of padding buys whole `[8, 128]` tiles: the
eight a of one (I, b) are the sublanes of one tile of the state, and phi(k)
of that tile is `k_b` times an aligned slice of k). The normaliser keeps
`Z = sum k k^T` whole, `[d, d]`: `q^T Z q = phi(q) . z` with both orders of
every pair, 64 KB a head beside a state of 4.5 MB.

`retention_step` is one position of every slot: on a TPU a Pallas kernel
(`retention_step` in a device trace) that reads each slot's state once and
writes it once, in place, building phi(k) and phi(q) a tile at a time in
VMEM and contracting the updated tile with the group's queries while it is
there; `retention_step_xla` is its twin in `jax.numpy`, the path off the
TPU. `retention_chunked` runs the rows of one prompt chunk from a given
state: the quadratic form inside the chunk, the state from the past, every
exponent a difference of cumulative log gates that is <= 0 (as in
`ops/kda.py`). XLA on every backend; phi(Q) of a chunk is built and
contracted with the state a block of phi at a time, so it passes through
HBM (357 MB a layer at 256 rows) and is never whole in memory.

Everything here is float32; the matmuls that write the state ask for the
highest precision, those a chunk's rows read it through for `_OUT`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_INTERPRET = False  # test-only: run the kernel in the Pallas interpreter
KERNEL, XLA = "retention_kernel", "xla"
BLOCK = 8           # channels a block of phi: the sublanes of a tile
SQRT2 = math.sqrt(2.0)
_HI = lax.Precision.HIGHEST
# what a chunk's rows read (scores, weighted sums, the state contracted with
# phi(q)) takes three bf16 passes and not six: 256 rows then took a layer
# 2.02 ms and not 2.71 on a v5e, and the outputs differ from the quadratic
# form's by 1e-4 of their largest, 40 times under the bf16 they are cast to
# (one pass: 1.55 ms and 7e-3; PERF.md section 6, PR 48). What is written
# to the state asks for `_HI`
_OUT = lax.Precision.HIGH


# ---------------------------------------------------------------------------
# the layout
# ---------------------------------------------------------------------------


def phi_width(d: int) -> int:
    assert d % BLOCK == 0, "the key width is a multiple of 8"
    return BLOCK * sum(d - BLOCK * i for i in range(d // BLOCK))


def _blocks(d: int):
    """(I, first channel, width n = d - 8I, first feature) of every block."""
    off = 0
    for i in range(d // BLOCK):
        n = d - BLOCK * i
        yield i, BLOCK * i, n, off
        off += BLOCK * n


def _weights(n: int):
    """The weight of b = 8I .. d-1 in block I: 1 inside the diagonal block,
    sqrt 2 past it."""
    return jnp.concatenate([jnp.ones((BLOCK,), jnp.float32),
                            jnp.full((n - BLOCK,), SQRT2, jnp.float32)])


def phi(x):
    """x [..., d] float32 -> [..., phi_width(d)] (the module docstring)."""
    d = x.shape[-1]
    parts = []
    for _, lo, n, _ in _blocks(d):
        xb = x[..., lo:] * _weights(n)
        parts.append((xb[..., :, None] * x[..., None, lo:lo + BLOCK]).reshape(
            x.shape[:-1] + (n * BLOCK,)))
    return jnp.concatenate(parts, axis=-1)


def unpack_state(S):
    """The state [..., W, dv] in the layout above -> `sum_j w_j k_j (x) k_j
    (x) v_j` [..., d, d, dv], symmetric in its first two channels: what a
    check holds to a direct sum. NumPy, on the host."""
    import numpy as np

    S = np.asarray(S, np.float32)
    W, dv = S.shape[-2:]
    d = next(d for d in range(BLOCK, 4096, BLOCK) if phi_width(d) == W)
    out = np.zeros(S.shape[:-2] + (d, d, dv), np.float32)
    for _, lo, n, off in _blocks(d):
        blk = S[..., off:off + n * BLOCK, :].reshape(
            S.shape[:-2] + (n, BLOCK, dv))          # [b, a, dv]
        w = np.asarray(_weights(n))[:, None, None]
        blk = blk / w
        out[..., lo:, lo:lo + BLOCK, :] = blk                     # [b, a]
        out[..., lo:lo + BLOCK, lo + BLOCK:, :] = np.swapaxes(
            blk[..., BLOCK:, :, :], -3, -2)                       # [a, b]
    return out


# ---------------------------------------------------------------------------
# the definition
# ---------------------------------------------------------------------------


def retention_quadratic(q, k, v, gamma, eps: float):
    """One sequence from position 0. q [T, KV, G, d] (scaled); k [T, KV, d];
    v [T, KV, dv]; gamma [T, KV] (log gates), float32 -> o [T, KV, G, dv].
    The definition, all T x T weights at once: for tests and short
    sequences."""
    T = q.shape[0]
    Gm = jnp.cumsum(gamma, axis=0)                              # [T, KV]
    le = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]       # j <= t
    diff = Gm[:, None, :] - Gm[None, :, :]                      # [t, j, KV]
    decay = jnp.where(le[..., None], jnp.exp(jnp.where(
        le[..., None], diff, 0.0)), 0.0)
    s = jnp.einsum("tkgd,jkd->tjkg", q, k, precision=_HI)
    a = s * s * decay[..., None]
    num = jnp.einsum("tjkg,jkv->tkgv", a, v, precision=_HI)
    return num / (jnp.sum(a, axis=1)[..., None] + eps)


# ---------------------------------------------------------------------------
# one position of every slot
# ---------------------------------------------------------------------------


def retention_step_xla(q, k, v, gate, S, Z, eps: float):
    """q [B, KV, G, d]; k [B, KV, d]; v [B, KV, dv]; gate [B, KV] = exp(gamma);
    S [B, KV, W, dv]; Z [B, KV, d, d], float32. Returns (o [B, KV, G, dv],
    S, Z after the position). Three passes over the state where the kernel
    makes one."""
    g = gate[..., None, None]
    S = g * S + phi(k)[..., None] * v[..., None, :]
    Z = g * Z + k[..., :, None] * k[..., None, :]
    num = jnp.einsum("bkgw,bkwv->bkgv", phi(q), S, precision=_HI)
    den = jnp.einsum("bkga,bkac,bkgc->bkg", q, Z, q, precision=_HI)
    return num / (den[..., None] + eps), S, Z


def step_path() -> str:
    """Which `retention_step` a decode step is built with, decided from the
    backend when the step is built."""
    return KERNEL if (jax.default_backend() == "tpu" or _INTERPRET) else XLA


def _step_kernel(slot_ref, layer_ref, x_ref, s_ref, z_ref,
                 o_ref, den_ref, s_out, z_out, kvo, kvo2, ql, ql2, *,
                 d: int, G: int):
    """One slot's KV head: x_ref [8, d] rows (k, v, gate on every lane,
    q_0 .. q_{G-1}); s_ref / s_out [W, dv] and z_ref / z_out [d, d], the
    same buffers. Scratch: kvo [d, dv] = k_b v (row b), ql [G, d, d'] with
    q_{h,b} on every lane of row b; kvo2, ql2 the same times sqrt 2."""
    del slot_ref, layer_ref
    rows = x_ref[0, 0]
    krow, vrow, grow = rows[0:1], rows[1:2], rows[2:3]
    # k_b on every lane of row b: the transpose of k on every sublane
    kb = jnp.broadcast_to(krow, (d, d)).T
    kvo[...] = kb * vrow
    kvo2[...] = kvo[...] * SQRT2
    z = grow * z_ref[0, 0, 0] + kb * krow
    z_out[0, 0, 0] = z
    qb = []
    for h in range(G):
        qrow = rows[3 + h:4 + h]
        qbh = jnp.broadcast_to(qrow, (d, d)).T
        ql[h] = qbh
        ql2[h] = qbh * SQRT2
        qb.append(qbh)
        den_ref[0, 0, h:h + 1, :] = jnp.sum(qbh * z * qrow, axis=0,
                                            keepdims=True)
    for h in range(G, BLOCK):
        den_ref[0, 0, h:h + 1, :] = jnp.zeros((1, d), jnp.float32)
    acc = [jnp.zeros((BLOCK, s_ref.shape[-1]), jnp.float32) for _ in range(G)]
    for _, lo, n, off in _blocks(d):
        kb_i = kb[lo:lo + BLOCK]                     # k_a on sublane a

        def tiles(u, part, *, kv_of, q_of, lo=lo, off=off, kb_i=kb_i):
            """The eight tiles of b = lo + 8u .. lo + 8u + 7."""
            b0 = pl.multiple_of(lo + u * BLOCK, BLOCK)
            kv8 = kv_of[pl.ds(b0, BLOCK), :]
            q8 = [q_of[h, pl.ds(b0, BLOCK), :] for h in range(G)]
            part = list(part)
            for j in range(BLOCK):
                r = pl.multiple_of(off + (u * BLOCK + j) * BLOCK, BLOCK)
                s = grow * s_ref[0, 0, 0, pl.ds(r, BLOCK), :] \
                    + kb_i * kv8[j:j + 1]
                s_out[0, 0, 0, pl.ds(r, BLOCK), :] = s
                for h in range(G):
                    part[h] = part[h] + q8[h][j:j + 1] * s
            return tuple(part)

        part = tiles(0, tuple(jnp.zeros_like(a) for a in acc),
                     kv_of=kvo, q_of=ql)
        if n > BLOCK:
            part = lax.fori_loop(
                1, n // BLOCK,
                functools.partial(tiles, kv_of=kvo2, q_of=ql2), part)
        acc = [a + qb[h][lo:lo + BLOCK] * p
               for h, (a, p) in enumerate(zip(acc, part))]
    for h in range(G):
        o_ref[0, 0, h:h + 1, :] = jnp.sum(acc[h], axis=0, keepdims=True)
    for h in range(G, BLOCK):
        o_ref[0, 0, h:h + 1, :] = jnp.zeros((1, s_ref.shape[-1]), jnp.float32)


def retention_step(q, k, v, gate, S, Z, layer, slots, eps: float):
    """The kernel. q [B, KV, G, d], k [B, KV, d], v [B, KV, dv], gate [B, KV]
    float32; S [L, B + 1, KV, W, dv] and Z [L, B + 1, KV, d, d], every layer's
    and every slot's, of which layer `layer` (a scalar) is read and written
    in place, row b at slot `slots[b]` (a slot that sits the step out is
    given the last one, which no sequence owns). Returns (o [B, KV, G, dv],
    S, Z)."""
    B, KV, G, d = q.shape
    dv = v.shape[-1]
    W = S.shape[-2]
    assert d == dv and d % 128 == 0 and G + 3 <= BLOCK, (
        "the kernel takes head widths that are whole lanes and a group of "
        "at most five query heads")
    x = jnp.concatenate(
        [k[:, :, None], v[:, :, None],
         jnp.broadcast_to(gate[:, :, None, None], (B, KV, 1, d)), q,
         jnp.zeros((B, KV, BLOCK - 3 - G, d), jnp.float32)], axis=2)

    def at_slot(b, g, slot_ref, layer_ref):
        return (layer_ref[0], slot_ref[b], g, 0, 0)

    def at_row(b, g, slot_ref, layer_ref):
        return (b, g, 0, 0)

    row = pl.BlockSpec((1, 1, BLOCK, d), at_row)
    state = pl.BlockSpec((1, 1, 1, W, dv), at_slot)
    norm = pl.BlockSpec((1, 1, 1, d, d), at_slot)
    o, den, S, Z = pl.pallas_call(
        functools.partial(_step_kernel, d=d, G=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV),
            in_specs=[row, state, norm],
            out_specs=[row, row, state, norm],
            scratch_shapes=[pltpu.VMEM((d, dv), jnp.float32),
                            pltpu.VMEM((d, dv), jnp.float32),
                            pltpu.VMEM((G, d, d), jnp.float32),
                            pltpu.VMEM((G, d, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, KV, BLOCK, dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, BLOCK, d), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(Z.shape, Z.dtype)],
        # operands count the two prefetched scalars: S is 3, Z is 4
        input_output_aliases={3: 2, 4: 3},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_INTERPRET,
        name="retention_step",
    )(slots.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      x, S, Z)
    den = jnp.sum(den[:, :, :G], axis=-1)
    return o[:, :, :G] / (den[..., None] + eps), S, Z


# ---------------------------------------------------------------------------
# the rows of one chunk, from a state
# ---------------------------------------------------------------------------


def retention_chunked(q, k, v, gamma, S, Z, eps: float):
    """C rows of one sequence that start from (S, Z). q [C, KV, G, d]
    (scaled); k [C, KV, d]; v [C, KV, dv]; gamma [C, KV]; S [KV, W, dv]; Z
    [KV, d, d], float32. Returns (o [C, KV, G, dv], S, Z after row C - 1). A
    row with gamma = 0 and k = 0 leaves the state as it was: pad with
    those (its own output is not read).

    Inside, everything is KV-head-major with a head's G x C query rows as
    one axis, so that every product is a batched matmul whose minor axes are
    rows and channels: with the group of five as the minor axis the scores
    alone were 25 times their size in padding (PERF.md section 6, PR 48)."""
    C, KV, G, d = q.shape
    dv = v.shape[-1]
    qh = jnp.transpose(q, (1, 2, 0, 3)).reshape(KV, G * C, d)   # row g C + i
    kh, vh = jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)        # [KV, C, .]
    Gm = jnp.cumsum(gamma, axis=0).T                             # [KV, C]
    le = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]        # j <= i
    diff = Gm[:, :, None] - Gm[:, None, :]                       # [KV, i, j]
    decay = jnp.where(le, jnp.exp(jnp.where(le, diff, 0.0)), 0.0)
    s = jnp.einsum("kqd,kjd->kqj", qh, kh, precision=_OUT)       # [KV, GC, C]
    a = (s * s).reshape(KV, G, C, C) * decay[:, None]
    num = jnp.einsum("kqj,kjv->kqv", a.reshape(KV, G * C, C), vh,
                     precision=_OUT)
    den = jnp.sum(a, axis=-1).reshape(KV, G * C)
    from_past = jnp.tile(jnp.exp(Gm), (1, G))                    # [KV, GC]
    to_end = jnp.exp(Gm[:, -1:] - Gm)                            # [KV, C]
    carried = jnp.exp(Gm[:, -1])[:, None, None]                  # [KV, 1, 1]
    kw = kh * to_end[..., None]
    den = den + from_past * jnp.einsum(
        "kqa,kac,kqc->kq", qh, Z, qh, precision=_OUT)
    Z = carried * Z + jnp.einsum("kja,kjc->kac", kw, kh, precision=_HI)
    past = jnp.zeros_like(num)
    new = []
    for _, lo, n, off in _blocks(d):
        w = _weights(n)
        blk = S[:, off:off + n * BLOCK].reshape(KV, n, BLOCK * dv)
        # the block's slice of phi(q), b-major as the state's rows are, and
        # one matmul over all of it: phi(Q) goes through HBM a block at a
        # time (357 MB a layer at 256 rows), which costs less than the
        # [rows, 8 dv] partial sums of contracting b first, then a: 1.64
        # against 1.96 ms a layer at 256 rows, 0.78 against 1.48 at 128
        # (v5e, PERF.md section 6, PR 48)
        pq = ((qh[..., lo:] * w)[..., :, None]
              * qh[..., None, lo:lo + BLOCK]).reshape(KV, G * C, n * BLOCK)
        past = past + jnp.einsum(
            "kqx,kxv->kqv", pq, S[:, off:off + n * BLOCK], precision=_OUT)
        kav = (kw[..., lo:lo + BLOCK, None] * vh[..., None, :]).reshape(
            KV, C, BLOCK * dv)
        upd = jnp.einsum("kjb,kjx->kbx", kh[..., lo:] * w, kav, precision=_HI)
        new.append((carried * blk + upd).reshape(KV, n * BLOCK, dv))
    o = (num + from_past[..., None] * past) / (den[..., None] + eps)
    o = jnp.transpose(o.reshape(KV, G, C, dv), (2, 0, 1, 3))
    return o, jnp.concatenate(new, axis=1), Z


# the TPU-only modules last: importing this file needs neither off the TPU
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

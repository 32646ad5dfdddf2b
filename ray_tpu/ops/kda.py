"""Delta-rule linear attention with a decay per channel (KDA): the chunked
scan a prefill runs and the one recurrence step a decode step runs.

Per head, with keys and queries of `dk` channels and values of `dv`, the
state is a `[dk, dv]` float32 matrix:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                     alpha_t = exp(g_t), g_t <= 0

`kda_step` is that line for a batch of slots. `kda_chunked` computes the
same over a whole sequence, a chunk of positions at a time (the WY form of
the delta rule): inside a chunk the updates u_s = beta_s (v_s - S_{s-1}^T
k_s) solve a unit lower-triangular system (I + Akk) u = rhs whose matrix
does not depend on the state, so only the state passes from chunk to chunk.
Every decay is the exponential of a sum of logs that is <= 0, so nothing
overflows however fast a channel forgets (a factorised `k / Gamma` would at
g = -5 a step).

On a TPU `kda_chunked` is one Pallas kernel (`kda_chunk` in a device trace):
a grid over eight heads at a time (the sublanes of a tile of `[T, H, dk]`,
which it reads as the layer's matmuls leave it) and, innermost, the
sequence's chunks of 128 positions. A head's state lives in VMEM from its
first chunk to its last. For a head's chunk, in VMEM: the sums of logs (block
by block, never a difference of two long sums); Akk and Aqk, element-wise on
the diagonals inside blocks of eight positions and as matmuls of two
bounded factors for the halves of blocks of 16, 32, 64, 128; (I + Akk)^-1 by
substitution (row after row inside the blocks of eight, then halving by
halving: a series in Akk's powers cancels to noise where keys repeat and
beta nears 2); the updates, o, the state. Nothing of a chunk but o goes to
HBM. The heads of a grid step go four at a time, as the leading axis of
every operation in the kernel's body: they are independent, so the compiler
runs one's matmul in the wait behind another's, and the body is traced and
lowered once for the four (what a program pays for holding the kernel, every
run: `tests/test_v5e_compile.py` pins its size). `kda_chunked_xla` is the
same mathematics in
`jax.numpy` (a `[H, C, C, dk]` tensor of decays under `lax.map`, a batched
triangular solve, a scan over chunks): the path off the TPU, the path of
heads narrower than a lane tile, and the twin the tests compare with.

Everything here is float32; the matmuls ask for the highest precision (a
TPU's default float32 matmul rounds its inputs to bf16).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_INTERPRET = False  # test-only: run the kernel in the Pallas interpreter
KERNEL, XLA = "kda_chunk_kernel", "xla"
CHUNK = 64          # positions a pass of the XLA form
_HI = lax.Precision.HIGHEST


def causal_conv_silu(x, tail, w):
    """Depthwise causal convolution over time, then SiLU. x [T, C]; tail
    [K-1, C], the inputs that came before x (zeros at a sequence's start);
    w [K, C], w[K-1] on the current position. Returns y [T, C]."""
    K = w.shape[0]
    xp = jnp.concatenate([tail.astype(x.dtype), x], axis=0)
    T = x.shape[0]
    y = sum(xp[j:j + T].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(K))
    return jax.nn.silu(y)


def kda_step(q, k, v, g, beta, state):
    """One position for a batch of slots. q, k, g [B, H, dk]; v [B, H, dv];
    beta [B, H]; state [B, H, dk, dv], all float32. Returns (o [B, H, dv],
    new state). Two passes over the state: one reads it (what the decayed
    state answers to k and to q), one reads and writes it."""
    decayed = jnp.exp(g)[..., None] * state
    u = jnp.sum(decayed * k[..., None], axis=-2)          # S'^T k
    w = jnp.sum(decayed * q[..., None], axis=-2)          # S'^T q
    d = beta[..., None] * (v - u)
    new = decayed + k[..., None] * d[..., None, :]
    o = w + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return o, new


def _chunk_matrices(q, k, g, beta):
    """For one chunk, all heads: q, k, g [H, C, dk], beta [H, C] ->
    (Akk, Aqk) [H, C, C]. Akk[s, r] = beta_s sum_c k_s k_r exp(G_s - G_r)
    for r < s; Aqk[t, r] the same with q_t and r <= t, no beta."""
    C = q.shape[1]
    G = jnp.cumsum(g, axis=1)
    s_idx = jnp.arange(C)
    le = s_idx[:, None] >= s_idx[None, :]                 # r <= s
    diff = G[:, :, None, :] - G[:, None, :, :]            # [H, s, r, dk]
    decay = jnp.where(le[None, :, :, None],
                      jnp.exp(jnp.where(le[None, :, :, None], diff, 0.0)), 0.0)
    kr = k[:, None, :, :] * decay
    akk = jnp.sum(k[:, :, None, :] * kr, axis=-1)
    aqk = jnp.sum(q[:, :, None, :] * kr, axis=-1)
    akk = jnp.where(s_idx[:, None] > s_idx[None, :], akk, 0.0) \
        * beta[:, :, None]
    return akk, aqk


def chunk_path(q, v) -> str:
    """KERNEL on a TPU (and in the interpreter, for tests) where a head's
    keys and values are whole lanes and the heads whole tiles of eight; the
    XLA form elsewhere. Read when a program that calls `kda_chunked` is
    traced."""
    on = jax.default_backend() == "tpu" or _INTERPRET
    fits = (q.shape[-1] % 128 == 0 and v.shape[-1] % 128 == 0
            and q.shape[1] % _HEADS == 0)
    return KERNEL if (on and fits) else XLA


def kda_chunked(q, k, v, g, beta, state):
    """A whole sequence. q, k, g [T, H, dk]; v [T, H, dv]; beta [T, H];
    state [H, dk, dv] (the state before position 0), all float32. Returns
    (o [T, H, dv], the state after position T-1). A position with beta = 0
    and g = 0 leaves the state as it was: pad with those. T is any length;
    how many positions go through at once is chosen here from the shapes."""
    if chunk_path(q, v) == KERNEL:
        return _kda_chunk_kernel(q, k, v, g, beta, state,
                                 interpret=_INTERPRET)
    return kda_chunked_xla(q, k, v, g, beta, state)


def kda_chunked_xla(q, k, v, g, beta, state):
    """`kda_chunked` in `jax.numpy`, `CHUNK` positions a pass: the chunk
    matrices of every chunk at once (a `[H, CHUNK, CHUNK, dk]` tensor of
    decays under `lax.map`), one batched triangular solve, a scan over
    chunks that carries the state."""
    T, H, dk = q.shape
    chunk = CHUNK
    n = -(-T // chunk)
    pad = n * chunk - T

    def chunks(x):
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        x = x.reshape((n, chunk) + x.shape[1:])
        return jnp.moveaxis(x, 2, 1)                       # [n, H, C, ...]

    qc, kc, vc, gc, bc = map(chunks, (q, k, v, g, beta))
    akk, aqk = lax.map(lambda xs: _chunk_matrices(*xs), (qc, kc, gc, bc))
    eye = jnp.eye(chunk, dtype=jnp.float32)
    # (I + Akk)^-1 for every chunk and head in one batched solve
    inv = lax.linalg.triangular_solve(
        eye + akk, jnp.broadcast_to(eye, akk.shape), left_side=True,
        lower=True, unit_diagonal=True)

    def body(S, xs):
        qi, ki, vi, gi, bi, inv_i, aqk_i = xs
        G = jnp.cumsum(gi, axis=1)                         # [H, C, dk]
        gamma = jnp.exp(G)
        rhs = bi[..., None] * (vi - jnp.einsum(
            "hck,hkv->hcv", ki * gamma, S, precision=_HI))
        u = jnp.einsum("hsr,hrv->hsv", inv_i, rhs, precision=_HI)
        o = jnp.einsum("hck,hkv->hcv", qi * gamma, S, precision=_HI) \
            + jnp.einsum("hsr,hrv->hsv", aqk_i, u, precision=_HI)
        to_end = jnp.exp(G[:, -1:, :] - G)                 # [H, C, dk]
        S = gamma[:, -1, :, None] * S + jnp.einsum(
            "hck,hcv->hkv", ki * to_end, u, precision=_HI)
        return S, o

    state, o = lax.scan(body, state, (qc, kc, vc, gc, bc, inv, aqk))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, -1)[:T]
    return o, state


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

_LEAF = 8        # positions whose decays among themselves are element-wise
_ROWS = 128      # positions a pass: the lanes of a chunk's `[C, C]` matrices
_HEADS = 8       # heads a grid step: the sublanes of a tile of `[T, H, dk]`
_TURN = 4        # of them a turn, as the leading axis of every operation


def _pieces(C: int):
    """Which piece of the chunk's `[C, C]` matrices computes entry (s, r),
    r <= s: d = s - r where both lie in one block of `_LEAF` positions
    (0 .. _LEAF-1); else `_LEAF + j` where the halving that parts them is
    into halves of `_LEAF << j` positions (s in a lower half of that size, r
    in the upper half beside it: the highest bit in which s and r differ).
    -1 above the diagonal. Made of iotas, which the compiler folds: a
    program's text holds no table."""
    s = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    r = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    differ = s ^ r
    halving, half = _LEAF - 1, _LEAF
    while half < C:
        halving = halving + (differ >= half)
        half *= 2
    return jnp.where(r > s, -1, jnp.where(differ < _LEAF, s - r, halving))


def _halvings(piece):
    """[levels, C/2, C] of ones and zeros: for halving j, which entries of
    the lower halves' rows (`_lower`) lie in the upper half beside them."""
    C = piece.shape[0]
    out, half = [], _LEAF
    while half < C:
        out.append(_lower(piece[None], half)[0] == _LEAF + len(out))
        half *= 2
    return jnp.stack(out).astype(jnp.float32)


def _dot(a, b, ca: int, cb: int):
    """a . b over axis `ca` of a and `cb` of b, head by head (axis 0 of
    both), float32 at the highest precision."""
    return lax.dot_general(a, b, (((ca,), (cb,)), ((0,), (0,))),
                           precision=_HI, preferred_element_type=jnp.float32)


def _halves(x, half: int):
    """x [B, C, W] as its blocks of `2 * half` positions, each an upper and
    a lower half: [B, C / (2 half), 2, half, W] (whole tiles: no data
    moves)."""
    B, C, W = x.shape
    return x.reshape(B, C // (2 * half), 2, half, W)


def _whole(x5):
    """`_halves` undone."""
    B, n, two, half, W = x5.shape
    return x5.reshape(B, n * two * half, W)


def _lower(x, half: int):
    """The rows of x [B, C, W] that lie in the lower half of a block of `2 *
    half` positions: [B, C/2, W]."""
    return _whole(_halves(x, half)[:, :, 1:])


def _to_lower(y, half: int):
    """`_lower` undone: y [B, C/2, W] on the lower halves' rows of [B, C,
    W], zeros on the upper halves'."""
    B, C2, W = y.shape
    y5 = y.reshape(B, C2 // half, 1, half, W)
    return _whole(jnp.concatenate([jnp.zeros_like(y5), y5], axis=2))


def _leaf_inverse(a, a_s, t_s):
    """(I + A)^-1 of every block of `_LEAF` positions at once, as one
    block-diagonal [B, C, C] matrix a head; a [B, C, W >= _LEAF]: lane d of
    row s holds A[s, s - d] (zero where s - d lies in another block). By
    substitution, row after row, T_j = e_j - sum_{i<j} A_ji T_i: row j of
    every block is one strided read of the scratch a_s [B, C, W], t_s [B, C,
    C]."""
    B, C, _ = t_s.shape
    n = C // _LEAF
    a_s[...] = a
    block = lax.broadcasted_iota(jnp.int32, (1, n, C), 1) * _LEAF
    at = lax.broadcasted_iota(jnp.int32, (1, n, C), 2) - block
    rows = []
    for j in range(_LEAF):
        t_j = jnp.broadcast_to((at == j).astype(jnp.float32), (B, n, C))
        if j:
            a_j = a_s[:, pl.ds(j, n, stride=_LEAF), :]          # [B, n, W]
            for i in range(j):
                t_j = t_j - a_j[:, :, j - i:j - i + 1] * rows[i]
        rows.append(t_j)
        t_s[:, pl.ds(j, n, stride=_LEAF), :] = t_j
    return t_s[...]


def _heads_chunk(q, k, v, g, beta, S, piece, halvings, a_s, t_s):
    """A chunk of C positions of B heads (the leading axis of everything
    here: the heads are independent, and the compiler runs one's matmul in
    the wait behind another's) from their states S [B, dv, dk], transposed
    (a decay is then one row over its lanes). q, k, g [B, C, dk]; v [B, C,
    dv]; beta [B, C, 1]; piece [C, C] (`_pieces`); halvings [levels, C/2, C]
    (`_halvings`); a_s [B, C, dk], t_s [B, C, C] scratch. Returns (o [B, C,
    dv], the states after the chunk).

    Every decay is the exponential of a sum of the logs it spans, made as
    such and not as a difference of two long sums: G holds each position's
    sum from the start of its block, of `_LEAF` positions at first, then of
    twice as many a halving.

    T = (I + Akk)^-1 by substitution, which no size of Akk's entries upsets
    (the powers of Akk that a series would sum grow as binomials where keys
    repeat and beta nears 2): inside the blocks of `_LEAF` positions row
    after row (`_leaf_inverse`), then a block twice as long from its halves,
    [[T1, 0], [-T2 A21 T1, T2]], a halving at a time. Of Akk only its
    diagonals inside a block and its lower halves' rows a halving are ever
    made; Aqk whole, for o."""
    B, C, W = q.shape
    at = lax.broadcasted_iota(jnp.int32, (1, C, 1), 1) % _LEAF  # in its block
    lane = lax.broadcasted_iota(jnp.int32, (1, 1, W), 2)
    G = g
    d = 1
    while d < _LEAF:
        G = G + jnp.where(at >= d, pltpu.roll(G, d, 1), 0.0)
        d *= 2
    diagonals = jnp.zeros((B, C, W), jnp.float32)    # lane d: Akk[s, s - d]
    aqk = jnp.zeros((B, C, C), jnp.float32)
    # positions d apart inside a block of _LEAF: exp(G_s - G_r), element-wise
    for d in range(_LEAF):
        k_r = pltpu.roll(k, d, 1) if d else k                  # row s: k_{s-d}
        G_r = pltpu.roll(G, d, 1) if d else G
        # (a row whose d-th neighbour back lies in the block before reads a
        # sum over that block, > 0, and is dropped below for it)
        kr = k_r * jnp.exp(G - G_r)
        if d:
            diagonals = jnp.where(
                lane == d, jnp.sum(k * kr, axis=2, keepdims=True), diagonals)
        aqk = jnp.where(piece == d, jnp.sum(q * kr, axis=2, keepdims=True),
                        aqk)
    # a row's block holds its d-th neighbour back where d <= s mod _LEAF
    inv = _leaf_inverse(jnp.where(lane <= at, diagonals * beta, 0.0),
                        a_s, t_s)
    # positions in the two halves of a block: exp(G_s - G_r) = (from r to
    # the upper half's end) x (from there to s), both <= 1 and the second
    # what G holds; the sum over channels a matmul of the two factors, for
    # the lower half's rows alone (the others' entries lie in other pieces)
    after = None                           # each position's sum of the logs
    #                                        behind it in its block
    half, j = _LEAF, 0
    while half < C:
        G5 = _halves(G, half)
        ends = G5[:, :, :, half - 1:, :]               # [B, n, 2, 1, W]
        to_end = ends - G5
        after = to_end if after is None else _halves(after, half)
        from_start = jnp.exp(_lower(G, half))
        both = _dot(jnp.concatenate([_lower(k, half) * from_start,
                                     _lower(q, half) * from_start], axis=1),
                    k * _whole(jnp.exp(to_end)), 2, 2)         # [B, C, C]
        # an upper half's positions have the lower half behind them too
        after = _whole(jnp.concatenate(
            [after[:, :, :1] + ends[:, :, 1:], after[:, :, 1:]], axis=2))
        here = halvings[j]
        a21 = both[:, :C // 2] * here * _lower(beta, half)
        aqk = aqk + _to_lower(both[:, C // 2:] * here, half)
        # the inverse of blocks twice as long: -T2 A21 T1 under T1, beside T2
        under = _dot(a21, inv, 2, 1)
        inv = inv - _to_lower(
            _dot(_lower(inv, half), _to_lower(under, half), 2, 1), half)
        # and their sums of logs: a lower half starts where the upper ended
        G = _whole(jnp.concatenate(
            [G5[:, :, :1], G5[:, :, 1:] + ends[:, :, :1]], axis=2))
        half, j = half * 2, j + 1
    gamma = jnp.exp(G)
    past = _dot(jnp.concatenate([k * gamma, q * gamma], axis=1), S, 2, 2)
    u = _dot(inv, beta * (v - past[:, :C]), 2, 1)              # [B, C, dv]
    S = gamma[:, C - 1:] * S + _dot(u, k * jnp.exp(after), 1, 1)
    return past[:, C:] + _dot(aqk, u, 2, 1), S


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, piece_ref, halv_ref,
                  s_ref, o_ref, s_out, st, a_s, t_s, *, interpret):
    """A chunk of C positions of `_HEADS` heads. q, k, g [C, _HEADS, dk]; v,
    o [C, _HEADS, dv]; beta [C, H]; s_ref / s_out [_HEADS, dk, dv], the
    heads' states before their first chunk and after their last; st
    [_HEADS, dv, dk] scratch, the states transposed, which live across the
    heads' chunks; a_s [_TURN, C, dk], t_s [_TURN, C, C] scratch of
    `_leaf_inverse`."""
    hb, c = pl.program_id(0), pl.program_id(1)
    C, H = beta_ref.shape

    def rows(ref, j):
        """Head j's C rows of a [C, _HEADS, W] block: sublane j of every
        tile, one strided access a tile's eight rows."""
        if interpret:                  # the interpreter reshapes no reference
            return ref.at[:, j, :]
        return ref.reshape(C * _HEADS, ref.shape[2]).at[
            pl.ds(j, C, stride=_HEADS), :]

    @pl.when(c == 0)
    def _():
        st[...] = jnp.swapaxes(s_ref[...], 1, 2)

    def turn(i, _):
        first = i * _TURN
        q, k, v, g = (jnp.stack([rows(ref, first + u)[...]
                                 for u in range(_TURN)])
                      for ref in (q_ref, k_ref, v_ref, g_ref))
        head = hb * _HEADS + first + lax.broadcasted_iota(
            jnp.int32, (_TURN, 1, H), 0)
        beta = jnp.sum(jnp.where(
            lax.broadcasted_iota(jnp.int32, (1, 1, H), 2) == head,
            beta_ref[...][None], 0.0), axis=2, keepdims=True)
        o, S = _heads_chunk(q, k, v, g, beta, st[pl.ds(first, _TURN)],
                            piece_ref[...], halv_ref[...], a_s, t_s)
        for u in range(_TURN):
            rows(o_ref, first + u)[...] = o[u]
        st[pl.ds(first, _TURN)] = S
        return 0

    lax.fori_loop(0, _HEADS // _TURN, turn, 0)

    @pl.when(c == pl.num_programs(1) - 1)
    def _():
        s_out[...] = jnp.swapaxes(st[...], 1, 2)


@functools.partial(jax.jit, static_argnames="interpret")
def _kda_chunk_kernel(q, k, v, g, beta, state, interpret: bool = False):
    """`kda_chunked` as the kernel: the arguments as they are (rows padded
    to whole chunks with beta = 0 and g = 0), the constants of `_pieces` and
    `_halvings` beside them. Jitted, so that a program's KDA layers share
    one trace and one lowering of the kernel's body."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = _ROWS
    n = -(-T // C)
    pad = n * C - T
    if pad:
        q, k, v, g, beta = (jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
                            for x in (q, k, v, g, beta))
    at_rows = lambda h, c: (c, h, 0)
    at_heads = lambda h, c: (h, 0, 0)
    key = pl.BlockSpec((C, _HEADS, dk), at_rows)
    val = pl.BlockSpec((C, _HEADS, dv), at_rows)
    heads = pl.BlockSpec((_HEADS, dk, dv), at_heads)
    piece = _pieces(C)
    halvings = _halvings(piece)
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, interpret=interpret),
        grid=(H // _HEADS, n),
        in_specs=[key, key, val, key, pl.BlockSpec((C, H), lambda h, c: (c, 0)),
                  pl.BlockSpec((C, C), lambda h, c: (0, 0)),
                  pl.BlockSpec(halvings.shape, lambda h, c: (0, 0, 0)),
                  heads],
        out_specs=[val, heads],
        out_shape=[jax.ShapeDtypeStruct((n * C, H, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HEADS, dv, dk), jnp.float32),
                        pltpu.VMEM((_TURN, C, dk), jnp.float32),
                        pltpu.VMEM((_TURN, C, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_chunk",
    )(q, k, v, g, beta, piece, halvings, state)
    return o[:T], state


# the TPU-only modules last: importing this file needs neither off the TPU
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

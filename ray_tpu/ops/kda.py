"""Delta-rule linear attention with a decay per channel (KDA): the chunked
scan a prefill runs and the one recurrence step a decode step runs.

Per head, with keys and queries of `dk` channels and values of `dv`, the
state is a `[dk, dv]` float32 matrix:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                     alpha_t = exp(g_t), g_t <= 0

`kda_step` is that line for a batch of slots. `kda_chunked` computes the
same over a whole sequence, `chunk` positions at a time (the WY form of the
delta rule): inside a chunk the updates u_s = beta_s (v_s - S_{s-1}^T k_s)
solve a unit lower-triangular system whose matrix does not depend on the
state, so it is built and inverted for every chunk at once, and the scan over
chunks carries only the state. Every decay is written as the exponential of
a difference of cumulative logs that is <= 0, so nothing overflows however
fast a channel forgets (a factorised `k / Gamma` would at g = -5 a step).

Everything here is float32; the matmuls that touch the state ask for the
highest precision (a TPU's default float32 matmul rounds its inputs to
bf16). Pure `jax.numpy`: XLA on every backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
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


def kda_chunked(q, k, v, g, beta, state, chunk: int = CHUNK):
    """A whole sequence. q, k, g [T, H, dk]; v [T, H, dv]; beta [T, H];
    state [H, dk, dv] (the state before position 0), all float32. Returns
    (o [T, H, dv], the state after position T-1). A position with beta = 0
    and g = 0 leaves the state as it was: pad with those."""
    T, H, dk = q.shape
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

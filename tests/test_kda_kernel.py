"""The delta rule's chunk pass as one kernel (ops/kda.py, `kda_chunk`): the
kernel in the Pallas interpreter on the CPU at the published head width (keys
and values of 128 channels, eight heads: one grid step's), held to the
recurrence one token after the other (benchmark/lib/reference_ling.py) and to
its twin in `jax.numpy`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_ling as ref
from ray_tpu.ops import kda

H, D = 8, 128


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(kda, "_INTERPRET", True)


def inputs(seed, T, beta_max=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = ref.l2_norm(jax.random.normal(ks[0], (T, H, D))) * D ** -0.5
    k = ref.l2_norm(jax.random.normal(ks[1], (T, H, D)))
    v = jax.random.normal(ks[2], (T, H, D))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (T, H, D)) * 2 - 2)
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    state = 0.3 * jax.random.normal(ks[5], (H, D, D))
    return q, k, v, g, beta, state


def close(got, want):
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_kernel_is_the_path_at_these_shapes():
    q, k, v, g, beta, s = inputs(0, 8)
    assert kda.chunk_path(q, v) == kda.KERNEL
    assert "kda_chunk" in str(jax.make_jaxpr(kda.kda_chunked)(q, k, v, g, beta, s))
    # sixteen channels a head, or four heads: the XLA form
    assert kda.chunk_path(q[..., :16], v[..., :16]) == kda.XLA
    assert kda.chunk_path(q[:, :4], v[:, :4]) == kda.XLA


def family_shapes():
    """(preset, kernel wanted, heads, a head's width) of both families that
    run `kda_chunked`, published and tiny."""
    from ray_tpu.models import ling, solar

    published = (ling.LingConfig(), solar.SolarConfig())
    tiny = (ling.LingConfig.tiny(), solar.SolarConfig.tiny())
    widths = lambda c: ((c.kda_heads, c.kda_head_dim)
                        if hasattr(c, "kda_heads") else (c.n_heads, c.head_dim))
    return ([(type(c).__name__, True) + widths(c) for c in published]
            + [(type(c).__name__ + ".tiny", False) + widths(c) for c in tiny])


@pytest.mark.parametrize("preset,kernel,heads,width", family_shapes())
def test_the_path_is_read_from_backend_and_shapes(monkeypatch, preset, kernel,
                                                  heads, width):
    """The kernel at both families' published shapes on a TPU, the XLA form
    at their tiny presets' and on any other backend: no flag, no name."""
    q = jax.ShapeDtypeStruct((256, heads, width), jnp.float32)
    assert (heads, width) in ((32, 128), (64, 128)) or not kernel
    monkeypatch.setattr(kda, "_INTERPRET", False)
    assert kda.chunk_path(q, q) == kda.XLA              # this is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kda.chunk_path(q, q) == (kda.KERNEL if kernel else kda.XLA)


@pytest.mark.parametrize("T", [1, 31, 32, 33, 128, 200, 256])
def test_kernel_equals_the_recurrence_and_its_twin(T):
    args = inputs(T, T)
    got = jax.jit(kda.kda_chunked)(*args)
    assert got[0].shape == (T, H, D)
    close(got, ref.kda_recurrence(*args))
    close(got, kda.kda_chunked_xla(*args))


def test_trailing_padded_rows_leave_the_state_bit_for_bit():
    q, k, v, g, beta, s = inputs(1, 256)
    real = 200
    valid = jnp.arange(256) < real
    _, padded = kda.kda_chunked(
        q, k, v, jnp.where(valid[:, None, None], g, 0.0),
        jnp.where(valid[:, None], beta, 0.0), s)
    _, alone = kda.kda_chunked(q[:real], k[:real], v[:real], g[:real],
                               beta[:real], s)
    np.testing.assert_array_equal(padded, alone)
    # a whole chunk of padding behind the real rows
    _, one = kda.kda_chunked(q[:128], k[:128], v[:128], g[:128], beta[:128], s)
    v128 = jnp.arange(256) < 128
    _, two = kda.kda_chunked(
        q, k, v, jnp.where(v128[:, None, None], g, 0.0),
        jnp.where(v128[:, None], beta, 0.0), s)
    np.testing.assert_array_equal(two, one)


@pytest.mark.parametrize("log_decay", [-5.0, -30.0])
def test_kernel_survives_the_fastest_decay(log_decay):
    """g = -5 (the families' bound) and -30 on every channel and position:
    every decay that is used is the exponential of a sum that is <= 0, so
    nothing overflows or turns NaN (at -30 the rows that look back into the
    block before read exp(+240) = inf, and are dropped for it)."""
    q, k, v, g, beta, s = inputs(2, 130)
    g = jnp.full_like(g, log_decay)
    got = kda.kda_chunked(q, k, v, g, beta, s)
    assert all(np.isfinite(np.asarray(x)).all() for x in got)
    close(got, ref.kda_recurrence(q, k, v, g, beta, s))


def test_beta_up_to_two():
    """Solar's `kda_allow_neg_eigval`: beta in (0, 2)."""
    args = inputs(3, 256, beta_max=2.0)
    assert float(args[4].max()) > 1.5
    got = kda.kda_chunked(*args)
    close(got, ref.kda_recurrence(*args))
    close(got, kda.kda_chunked_xla(*args))


def test_keys_that_repeat_under_beta_near_two():
    """What a served model's keys look like (a direction a head's keys
    share, `k_s . k_r` ~ 0.6) under Solar's beta: Akk's entries are ~1, the
    powers of Akk that a series for (I + Akk)^-1 would sum grow as binomials
    and cancel to nothing in float32 (the first kernel of PR 50 read the
    state 3e-3 off at 0.15 and served noise on the chip); substitution does
    not care."""
    q, k, v, g, beta, s = inputs(6, 256, beta_max=2.0)
    common = jax.random.normal(jax.random.PRNGKey(7), (1, H, D))
    k = ref.l2_norm(common + 0.7 * k * D ** 0.5)
    assert 0.4 < float(jnp.einsum("thd,thd->th", k[1:], k[:-1]).mean()) < 0.9
    g = 0.1 * g                                  # slow decays: the past counts
    got = kda.kda_chunked(q, k, v, g, beta, s)
    close(got, ref.kda_recurrence(q, k, v, g, beta, s))
    close(got, kda.kda_chunked_xla(q, k, v, g, beta, s))


def test_two_calls_that_hand_the_state_on_equal_one():
    q, k, v, g, beta, s = inputs(4, 328)
    o, end = kda.kda_chunked(q, k, v, g, beta, s)
    o1, mid = kda.kda_chunked(q[:200], k[:200], v[:200], g[:200], beta[:200], s)
    o2, end2 = kda.kda_chunked(q[200:], k[200:], v[200:], g[200:], beta[200:],
                               mid)
    close((jnp.concatenate([o1, o2]), end2), (o, end))


def test_kda_step_continues_a_state_the_kernel_left():
    q, k, v, g, beta, s0 = inputs(5, 80)
    _, s = kda.kda_chunked(q[:70], k[:70], v[:70], g[:70], beta[:70], s0)
    outs = []
    for t in range(70, 80):
        o, s = kda.kda_step(q[t][None], k[t][None], v[t][None], g[t][None],
                            beta[t][None], s[None])
        s = s[0]
        outs.append(o[0])
    o_ref, s_ref = ref.kda_recurrence(q, k, v, g, beta, s0)
    close((jnp.stack(outs), s), (o_ref[70:], s_ref))

"""The grouped SwiGLU (ops/grouped_ffn.py): the kernel in the Pallas
interpreter against a dense SwiGLU an expert, at small widths on the CPU, and
the kernel and its twin held to each other."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import grouped_ffn as gf

D, F = 256, 128
# (rows, rows per expert): what is asserted on is the held rows alone
CASES = {
    # 512 pairs of 64 slots, a quarter held, an expert seldom holding two
    "ling_decode_like": (512, [1, 0, 2, 1, 0, 0, 4, 1, 1, 0, 3, 1, 0, 1, 2, 1,
                               0, 2, 1, 1, 5, 0, 1, 1, 0, 1, 1, 2, 0, 1, 1, 1]),
    # a chunk's rows: a few experts with tens of rows, one with most
    "solar_chunk_like": (640, [17, 0, 31, 0, 0, 9, 60, 0, 3, 22]),
    "empty_groups_between_touched": (256, [0, 0, 7, 0, 0, 0, 5, 0, 0, 1, 0]),
    "one_expert_holds_every_row": (512, [0, 0, 300, 0]),
    "no_held_row": (128, [0, 0, 0, 0]),
    # the held rows end inside the second tile, unheld rows behind them
    "held_rows_end_inside_a_tile": (384, [100, 60, 0, 13]),
    # fewer rows than a tile, and not a multiple of the sublanes
    "fewer_rows_than_a_tile": (24, [5, 0, 7, 1]),
}


def inputs(rows, counts, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    E = len(counts)
    x = jax.random.normal(k[0], (rows, D), jnp.float32).astype(dtype)
    w1 = (jax.random.normal(k[1], (E, D, F)) / D ** 0.5).astype(dtype)
    w3 = (jax.random.normal(k[2], (E, D, F)) / D ** 0.5).astype(dtype)
    w2 = (jax.random.normal(k[3], (E, F, D)) / F ** 0.5).astype(dtype)
    return x, w1, w3, w2, jnp.asarray(counts, jnp.int32)


def dense(x, w1, w3, w2, counts):
    """Each expert's rows through its own SwiGLU, float32 on the host:
    ([held, D], held)."""
    x, w1, w3, w2 = (np.asarray(a, np.float32) for a in (x, w1, w3, w2))
    out, lo = [], 0
    for e, c in enumerate(np.asarray(counts)):
        r = x[lo:lo + c]
        gate = r @ w1[e]
        out.append((gate / (1 + np.exp(-gate)) * (r @ w3[e])) @ w2[e])
        lo += c
    return np.concatenate(out), lo


@pytest.fixture()
def interpreter(monkeypatch):
    monkeypatch.setattr(gf, "_INTERPRET", True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_equals_a_dense_swiglu_an_expert(interpreter, case, dtype):
    rows, counts = CASES[case]
    args = inputs(rows, counts, dtype)
    assert gf.ffn_path(args[1]) == gf.KERNEL
    y = jax.jit(gf.grouped_ffn)(*args)
    assert y.shape == (rows, D) and y.dtype == dtype
    want, held = dense(*args)
    # bf16: one rounding of the result (|y| up to ~4: half a step is 0.016)
    # and of silu(gate) * up before the down projection
    np.testing.assert_allclose(np.asarray(y, np.float32)[:held], want,
                               atol=1e-5 if dtype == jnp.float32 else 0.04)


def test_the_visits_are_the_pairs_of_tile_and_expert_with_a_row():
    """100 + 60 + 0 + 13 rows in tiles of 128: expert 0 in tile 0, expert 1
    in tiles 0 and 1, expert 3 in tile 1; none for the empty expert and none
    for the third tile, whose rows no expert holds."""
    counts = jnp.asarray([100, 60, 0, 13], jnp.int32)
    offsets, expert, tile, n = gf.visits(counts, 384, 128)
    assert int(n) == 4 and expert.shape == tile.shape == (3 + 4 - 1,)
    assert offsets.tolist() == [0, 100, 160, 160, 173]
    assert expert[:4].tolist() == [0, 1, 1, 3]
    assert tile[:4].tolist() == [0, 0, 1, 1]


@pytest.mark.parametrize("case", ["ling_decode_like", "solar_chunk_like"])
def test_the_twin_and_the_kernel_agree_on_the_same_inputs(interpreter, case):
    rows, counts = CASES[case]
    args = inputs(rows, counts, jnp.float32, seed=1)
    held = sum(counts)
    np.testing.assert_allclose(
        jax.jit(gf.grouped_ffn_kernel)(*args)[:held],
        jax.jit(gf.grouped_ffn_xla)(*args)[:held], atol=1e-5)


def test_the_path_is_the_twin_off_the_tpu_and_for_experts_too_large(
        monkeypatch):
    """Off the TPU the twin; on it (the interpreter stands in) the kernel
    where an expert's three matrices fit VMEM twice over, as Ling's and
    Solar-Open2's do, and the twin where they cannot."""
    w = jax.ShapeDtypeStruct
    ling, solar = w((128, 2560, 768), jnp.bfloat16), w((40, 4096, 1280),
                                                       jnp.bfloat16)
    wide = w((8, 4096, 14336), jnp.bfloat16)
    assert gf.ffn_path(ling) == gf.XLA
    monkeypatch.setattr(gf, "_INTERPRET", True)
    assert gf.ffn_path(ling) == gf.ffn_path(solar) == gf.KERNEL
    assert gf.ffn_path(wide) == gf.XLA

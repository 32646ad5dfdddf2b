"""The SwiGLU of many experts over rows sorted by expert: for the rows
`[offset_e, offset_e + counts_e)` of expert e,

    y = (silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]

`grouped_ffn` is what a caller uses. On a TPU it is one Pallas kernel
(`grouped_ffn` in a device trace); off it, and where an expert's matrices
are too large for the kernel, `grouped_ffn_xla`, its twin: three
`lax.ragged_dot`s over the whole rows.

**The kernel.** With a handful of rows an expert the time is the weights'
bytes (XLA's grouped matmul moved them at half of what the chip's memory
gives, PERF.md section 6, PR 49), so the kernel is built around reading each
touched expert's three matrices from HBM once, each as one contiguous block,
with the matmuls of one expert under the DMA of the next:

- The rows are cut into tiles of `ROW_TILE`. A *visit* is one (row tile,
  expert) pair in which the expert has a row. The list of visits, in the
  rows' order, is made from `counts` (`visits`: a few small XLA operations)
  and handed to the kernel by scalar prefetch, and the grid is as long as
  that list and no longer: an expert with no row is never visited, a tile
  past the last held row neither, and its rows come back undefined.
- A visit is one step of the grid. Its blocks are the tile's rows and the
  expert's `w1`, `w3`, `w2` whole; it computes gate and up with float32
  accumulation, `silu(gate) * up` in float32, casts once, and the down
  projection. The pipeline's second buffers take the next visit's expert
  meanwhile: a visit's matmuls (the MXU's time for a weight tile does not
  fall below that of 128 rows: 128 operations a weight element against the
  chip's 240) stay under the next expert's DMA. An expert whose rows cross
  a tile's end is visited twice in a row, and the second visit finds its
  blocks where they are.
- The visits of one row tile are consecutive, so the tile's rows are fetched
  once and its output block stays in VMEM until the last of them: each visit
  writes its own expert's rows into it (a select by row index), and the
  block goes back to HBM once.

Whole matrices, double-buffered, are 24 MB of VMEM at Ling's widths and 63
MB at Solar-Open2's, of the v5e's 128 MiB. Blocks of a matrix over a second
grid axis, accumulators in scratch, were measured and are not kept: they
read 72 to 79% of the bytes' time where whole matrices read 81 to 91%,
because a visit's last block has nothing in flight behind it and an expert
that crosses a tile's end is streamed twice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INTERPRET = False  # test-only: run the kernel in the Pallas interpreter
KERNEL, XLA = "grouped_ffn_kernel", "xla"
ROW_TILE = 128
VMEM_LIMIT_BYTES = 100 << 20    # of the v5e's 128 MiB
# what of it an expert's matrices may take, twice over (the pipeline's two
# buffers): the rest is the rows', the results' and the matmuls' own
WEIGHTS_VMEM_BYTES = 75 << 20


def ffn_path(w1) -> str:
    """KERNEL on a TPU (and in the interpreter, for tests) where an expert's
    three matrices fit VMEM twice, XLA elsewhere: read when a step is
    traced. w1 [E, D, F]."""
    fits = (6 * w1.shape[1] * w1.shape[2] * w1.dtype.itemsize
            <= WEIGHTS_VMEM_BYTES)
    on = jax.default_backend() == "tpu" or _INTERPRET
    return KERNEL if (on and fits) else XLA


def grouped_ffn(rows, w1, w3, w2, counts):
    """rows [M, D] sorted by expert, the rows of no expert last; w1, w3
    [E, D, F]; w2 [E, F, D]; counts [E] int32 rows per expert. -> [M, D] in
    rows' dtype; rows past sum(counts) come back undefined."""
    if ffn_path(w1) == KERNEL:
        return grouped_ffn_kernel(rows, w1, w3, w2, counts)
    return grouped_ffn_xla(rows, w1, w3, w2, counts)


def grouped_ffn_xla(rows, w1, w3, w2, counts):
    up = jax.nn.silu(lax.ragged_dot(rows, w1, counts)) \
        * lax.ragged_dot(rows, w3, counts)
    return lax.ragged_dot(up, w2, counts)


def visits(counts, M: int, tm: int):
    """The (row tile, expert) pairs in which the expert has a row, in the
    rows' order. -> (offsets [E + 1], expert [W], tile [W], number of
    visits), W = M // tm + E - 1 their static bound; entries past the number
    are padding."""
    E = counts.shape[0]
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // tm
    met = jnp.where(counts > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(met)
    w = jnp.arange(M // tm + E - 1, dtype=jnp.int32)
    expert = jnp.minimum(
        jnp.sum((upto[None, :] <= w[:, None]).astype(jnp.int32), axis=1),
        E - 1)
    tile = jnp.clip(first[expert] + w - (upto - met)[expert], 0, M // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, expert, tile, upto[-1]


def _kernel(offsets, expert, tile, x_ref, w1_ref, w3_ref, w2_ref, o_ref):
    w = pl.program_id(0)
    x = x_ref[...]
    gate = jnp.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    up = jnp.dot(x, w3_ref[...], preferred_element_type=jnp.float32)
    h = (jax.nn.silu(gate) * up).astype(x.dtype)
    y = jnp.dot(h, w2_ref[...], preferred_element_type=jnp.float32)
    e = expert[w]
    row = tile[w] * x.shape[0] + lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = (row >= offsets[e]) & (row < offsets[e + 1])
    o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def grouped_ffn_kernel(rows, w1, w3, w2, counts):
    M, D = rows.shape
    F = w1.shape[2]
    tm = min(ROW_TILE, -(-M // 16) * 16)
    pad = -M % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    offsets, expert, tile, n = visits(counts, M + pad, tm)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct(rows.shape, rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # a call with no held row at all still makes one (empty) visit
            grid=(jnp.maximum(n, 1),),
            in_specs=[
                pl.BlockSpec((tm, D), lambda w, o, e, t: (t[w], 0)),
                pl.BlockSpec((None, D, F), lambda w, o, e, t: (e[w], 0, 0)),
                pl.BlockSpec((None, D, F), lambda w, o, e, t: (e[w], 0, 0)),
                pl.BlockSpec((None, F, D), lambda w, o, e, t: (e[w], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, D), lambda w, o, e, t: (t[w], 0))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_INTERPRET,
        name="grouped_ffn",
    )(offsets, expert, tile, rows, w1, w3, w2)
    return out[:M] if pad else out

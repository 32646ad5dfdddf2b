"""Decode attention over a paged KV cache, in Pallas for TPU.

One query token a slot against that slot's *live* context, read in place
from the block pool: the kernel walks only ``ceil(length / BS)`` entries of
the slot's block table, so a step costs what the live keys and values cost
and not ``max_model_len`` positions of every slot.

* The pool stays in HBM in the engine's layout ``[L, NB, BS, KV, HD]``, seen
  as ``[L * NB, BS * KV, HD]`` (the same bytes): a page is one contiguous
  ``[BS * KV, HD]`` tile that holds all KV heads of its ``BS`` tokens, and
  layer ``l``'s pages start at ``l * NB``.
* Pages move by DMA in groups of ``G`` into double-buffered VMEM; the next
  group (of this slot, or the first of the next slot) is in flight while the
  current one is scored. A page past the live length is never fetched.
* All KV heads of a group are scored in one matmul: ``q [H, HD]`` against the
  group's rows ``[G * BS * KV, HD]`` gives ``[H, rows]``, of which a query
  head keeps the columns of its own KV head (a mask, no ``repeat``, no copy
  of the cache). K and V pass through the MXU as stored, in the cache's
  dtype; scores, softmax and the accumulator are float32 (online softmax).

Off-TPU (CPU tests) `decode_attention` runs `xla_decode_attention`, the
gather / repeat / dense-scores formulation the kernel replaces and is tested
against. Which one a decode step uses is decided when the step is built
(`decode_path`), from the backend and the shapes alone.

`chunk_attention` is the other reader of the pool: the rows of one prompt
chunk that rides in a decode step, over their own sequence's table, in XLA
on every backend; `chunk_latent_attention` is the same for a pool of latents
(`llm/_joyai_steps.py`).

**A pool of latents** (`paged_latent_attention`, the decode rows of
`llm/_joyai_steps.py` and `llm/_ling_steps.py`): one cached row a position,
the key of every head, whose first `rank` columns are the value. The same
kernel with the pool as its one HBM operand (`value_dim`, static): a live
page moves to VMEM once, one DMA and one buffer, and the weighted sum reads
the value where the key lies. Callers with keys and values in two arrays
trace the program they traced before it existed.

**A window** (`window`, a static number of positions, on all three): a row
at position p sees the keys at p - window < j <= p and nothing before them.
The table is then read as a *ring*: the block that holds position j is entry
``(j // BS) mod max_blocks`` of the slot's row, so a table of a few blocks
(a slot's own, fixed: `llm/_mellum_steps.py`) serves a sequence of any
length whose writer puts position j at ``j mod (max_blocks * BS)``. The
kernel starts at the window's first page and fetches none behind it. A
table as long as the sequence is a ring that never wraps. Callers that
give no window trace the programs they traced before it existed.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_INTERPRET = False  # test-only: run the kernel in the Pallas interpreter

NEG_INF = -1e30
KERNEL, XLA = "paged_kernel", "xla"
# rows of K (or V) scored at once: 8 pages of 16 tokens x 8 KV heads
_GROUP_ROWS = 1024
_MAX_GROUP_PAGES = 16


# ---------------------------------------------------------------------------
# XLA: the decode reference (the only path off-TPU) and the chunk's attention
# ---------------------------------------------------------------------------


def xla_decode_attention(q, kc, vc, layer, tables, lengths, window=None):
    """q [B, H, HD]; kc/vc [L, NB, BS, KV, HD], of which layer `layer`
    (a scalar) is read; tables [B, max_blocks]; lengths [B] (0 = inactive
    slot) → o [B, H, HD]. Gathers every block of every table row and scores
    all ``max_blocks * BS`` positions. With a `window` the last `window`
    positions alone count, and the table is a ring (module docstring)."""
    B, H, hd = q.shape
    kcl, vcl = kc[layer], vc[layer]
    _, bs, kvh, _ = kcl.shape
    Lmax = tables.shape[1] * bs
    if window is None:
        valid = jnp.arange(Lmax)[None, :] < lengths[:, None]     # [B,Lmax]
    else:
        # the position a ring index holds: the newest that lands on it
        last = lengths[:, None] - 1
        pos = last - jnp.mod(last - jnp.arange(Lmax)[None, :], Lmax)
        valid = (pos >= 0) & (pos > last - window)
    # paged gather: [B, max_blocks, BS, KV, HD] → [B, Lmax, KV, HD]
    k_all = kcl[tables].reshape(B, Lmax, kvh, hd)
    v_all = vcl[tables].reshape(B, Lmax, kvh, hd)
    if kvh != H:
        k_all = jnp.repeat(k_all, H // kvh, axis=2)
        v_all = jnp.repeat(v_all, H // kvh, axis=2)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q[:, None], k_all,
        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)[:, 0]


def chunk_attention(q, kc, vc, layer, row, qpos, end, tile: int = 512,
                    window=None):
    """Causal attention of one sequence's prompt chunk over its own block
    table: q [C, H, HD] at absolute positions qpos [C]; kc/vc [L, NB, BS, KV,
    HD], of which layer `layer` (a scalar) is read after the chunk's own keys
    were scattered into it; row [max_blocks] the sequence's table row. Row i
    sees the keys at positions <= qpos[i]: cached prefix, earlier chunks and
    its own chunk alike. Keys are read `tile` positions at a time up to `end`
    (the chunk's last position + 1) with an online softmax, so the scores
    cost what the live context costs and not max_model_len. With a `window`
    row i sees qpos[i] - window < j <= qpos[i], the tiles start at the
    block of the first row's oldest key, and `row` is a ring (module
    docstring) that must hold window + C - 1 positions: the chunk's keys
    are written before its rows attend. -> [C, H, HD]."""
    C, H, hd = q.shape
    L, NB, bs, kvh, _ = kc.shape
    rep = H // kvh
    blocks = max(1, min(tile, row.shape[0] * bs) // bs)   # blocks a tile
    tile = blocks * bs
    ring = row.shape[0]
    first = 0                                  # the first block a tile reads
    if window is None:
        row = jnp.pad(row, (0, -ring % blocks)) + layer * NB
    else:
        row = row + layer * NB
        first = jnp.maximum(qpos[0] - window + 1, 0) // bs
    k_pages = kc.reshape(L * NB, bs, kvh, hd)             # the same bytes
    v_pages = vc.reshape(L * NB, bs, kvh, hd)
    # a query head next to the others of its KV head: no repeat of K or V
    qg = q.reshape(C, kvh, rep, hd)
    scale = 1.0 / math.sqrt(hd)

    def one_tile(t, carry):
        m, l, acc = carry
        if window is None:
            at = lax.dynamic_slice(row, (t * blocks,), (blocks,))
        else:
            at = row[(first + t * blocks + jnp.arange(blocks)) % ring]
        k = k_pages[at].reshape(tile, kvh, hd)
        v = v_pages[at].reshape(tile, kvh, hd)
        s = jnp.einsum("ckrd,wkd->krcw", qg, k,
                       preferred_element_type=jnp.float32) * scale
        if window is None:
            seen = (t * tile + jnp.arange(tile))[None, :] <= qpos[:, None]
        else:
            pos = ((first + t * blocks) * bs + jnp.arange(tile))[None, :]
            seen = (pos <= qpos[:, None]) & (pos > qpos[:, None] - window)
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "krcw,wkd->krcd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((kvh, rep, C, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((kvh, rep, C, 1), jnp.float32)
    acc0 = jnp.zeros((kvh, rep, C, hd), jnp.float32)
    if window is not None:
        end = end - first * bs
    _, l, acc = lax.fori_loop(0, (end + tile - 1) // tile, one_tile,
                              (m0, l0, acc0))
    o = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)     # [KV, rep, C, HD]
    return o.transpose(2, 0, 1, 3).reshape(C, H, hd)


def chunk_latent_attention(q, pool, layer, row, qpos, end, rank: int,
                           tile: int = 1024):
    """`chunk_attention` for latent attention in its absorbed form: the rows
    of one prompt chunk over their sequence's cached latents, each a key of
    all heads whose value is its first `rank` columns. q [C, H, W] (the
    absorbed query, scaled, zeros past rank + rope) at absolute positions
    qpos [C]; pool [L, NB, BS, 1, W], of which layer `layer` (a scalar) is
    read after the chunk's own latents were scattered into it; row
    [max_blocks] the sequence's table row. Row i sees the latents at
    positions <= qpos[i]: blocks another sequence left, earlier chunks and its
    own chunk alike, `tile` positions at a time up to `end` with an online
    softmax. Every head's query is a row of one [C * H, W] x [W, tile]
    matmul: 2 C H (W + rank) operations a position read. -> [C, H, rank].

    On a v5e at C = 256, H = 32, W = 640, rank = 512 over 12,000 positions a
    layer takes 1.59 ms with tiles of 512, 1.54 with 1,024, 1.50 with 2,048
    (134 to 142 TFLOP/s of these operations); the expanded form, keys and
    values of every head made from each tile's latents (2 x 512 x 8,192
    operations a position, then 2 C H 320), 1.54 and 1.45 with tiles of 512
    and 1,024 (PERF.md section 6, PR 60): within a tenth, so the chunk's rows
    share the decode rows' absorbed query and output."""
    C, H, W = q.shape
    L, NB, bs = pool.shape[:3]
    blocks = max(1, min(tile, row.shape[0] * bs) // bs)   # blocks a tile
    tile = blocks * bs
    row = jnp.pad(row, (0, -row.shape[0] % blocks)) + layer * NB
    pages = pool.reshape(L * NB, bs, W)                   # the same bytes
    qf = q.reshape(C * H, W)
    qrow = jnp.repeat(qpos, H)[:, None]                   # [C * H, 1]

    def one_tile(t, carry):
        m, l, acc = carry
        at = lax.dynamic_slice(row, (t * blocks,), (blocks,))
        lat = pages[at].reshape(tile, W)
        s = jnp.einsum("qw,kw->qk", qf, lat,
                       preferred_element_type=jnp.float32)
        seen = (t * tile + jnp.arange(tile))[None, :] <= qrow
        s = jnp.where(seen, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(p.astype(lat.dtype), lat[:, :rank],
                                    preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((C * H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((C * H, 1), jnp.float32)
    acc0 = jnp.zeros((C * H, rank), jnp.float32)
    _, l, acc = lax.fori_loop(0, (end + tile - 1) // tile, one_tile,
                              (m0, l0, acc0))
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype).reshape(C, H, rank)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------


def _decode_kernel(tables_ref, lengths_ref, first_ref, *refs, scale,
                   group_pages, page_rows, block_size, max_blocks, window,
                   value_dim):
    """One grid step = one slot. tables_ref [B * max_blocks], lengths_ref
    [B], first_ref [1] (the layer's first page in the pool) in SMEM;
    q_ref/o_ref [1, Hp, HD]; token_ref [Hp, rows] (`_column_tokens`);
    k_hbm/v_hbm [L * NB, page_rows, HD] in HBM; k_buf/v_buf [2, rows, HD]
    with rows = group_pages * page_rows; sems [2, 2] (K/V x buffer);
    parity_ref [1]: the buffer the next group lands in, carried from slot
    to slot. With a `window` (static) a slot's pages are counted from the
    block of its first live position, length - window, and its table is a
    ring: three scalar operations a page and one comparison a group more,
    and none of them traced without one. With a `value_dim` (static) the
    pool is its own value: there is no v_hbm and no v_buf, sems is [1, 2],
    a page moves once, a row's value is its first `value_dim` columns where
    they lie in k_buf, and o_ref is [1, Hp, value_dim]; without one the
    body is the two-array one, equation for equation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if value_dim is None:
        (q_ref, token_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
         parity_ref) = refs
    else:
        q_ref, token_ref, k_hbm, o_ref, k_buf, sems, parity_ref = refs
    G, bs = group_pages, block_size
    b, B = pl.program_id(0), pl.num_programs(0)

    def first_block(slot):
        """The block of the slot's oldest live position."""
        return lax.div(jnp.maximum(lengths_ref[slot] - window, 0), bs)

    def pages_of(slot):
        pages = lax.div(lengths_ref[slot] + (bs - 1), bs)
        return pages if window is None else pages - first_block(slot)

    def each_page(slot, g, buf, act):
        """`act` on the K and the V copy (the one copy of a pool that is
        its own value) of every live page of group g of `slot`: nothing is
        done, and the table is not read, from the first page past the live
        length on."""
        left = pages_of(slot) - g * G
        if window is not None:
            first = first_block(slot) + g * G
        for i in range(G):
            @pl.when(i < left)
            def _(i=i):
                if window is None:
                    page = first_ref[0] + tables_ref[
                        slot * max_blocks + g * G + i]
                else:
                    page = first_ref[0] + tables_ref[
                        slot * max_blocks + lax.rem(first + i, max_blocks)]
                dst = pl.ds(i * page_rows, page_rows)
                act(pltpu.make_async_copy(
                    k_hbm.at[page], k_buf.at[buf, dst], sems.at[0, buf]))
                if value_dim is None:
                    act(pltpu.make_async_copy(
                        v_hbm.at[page], v_buf.at[buf, dst], sems.at[1, buf]))

    def start(slot, g, buf):
        each_page(slot, g, buf, lambda copy: copy.start())

    def wait(slot, g, buf):
        each_page(slot, g, buf, lambda copy: copy.wait())

    @pl.when(b == 0)
    def _():
        # rows that no DMA fills are masked out of the scores, and their
        # probability 0 must meet a finite V: no stale NaN in the buffers
        k_buf[...] = jnp.zeros_like(k_buf)
        if value_dim is None:
            v_buf[...] = jnp.zeros_like(v_buf)
        parity_ref[0] = 0
        start(0, 0, 0)

    length = lengths_ref[b]
    # an inactive slot still takes one (empty) turn, so that the chain of
    # prefetches from slot to slot is never broken
    n_groups = jnp.maximum(lax.div(pages_of(b) + (G - 1), G), 1)
    q = q_ref[0]                                             # [Hp, HD]
    token = token_ref[...]                                   # [Hp, rows]
    if window is not None:
        first = first_block(b)

    def group(g, carry):
        m, l, acc = carry
        buf = parity_ref[0]
        nxt = 1 - buf
        more = g + 1 < n_groups

        @pl.when(more)
        def _():
            start(b, g + 1, nxt)

        @pl.when(jnp.logical_not(more) & (b + 1 < B))
        def _():
            start(b + 1, 0, nxt)

        wait(b, g, buf)
        k = k_buf[buf]                                       # [rows, HD]
        v = v_buf[buf] if value_dim is None else k[:, :value_dim]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if window is None:
            valid = token < length - g * (G * bs)
        else:
            # the group's first position, then the live ones from it
            at = (first + g * G) * bs
            valid = (token < length - at) & (token >= length - window - at)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        parity_ref[0] = nxt
        return m_new, l, acc

    Hp, vd = o_ref.shape[1:]
    m0 = jnp.full((Hp, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hp, 1), jnp.float32)
    acc0 = jnp.zeros((Hp, vd), jnp.float32)
    _, l, acc = lax.fori_loop(0, n_groups, group, (m0, l0, acc0))
    # an inactive slot (and a padded query head) has l == 0 and acc == 0
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _sublane_tile(dtype) -> int:
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _column_tokens(Hp: int, rows: int, n_heads: int, kv_heads: int):
    """[Hp, rows] int32: row r of a group of pages is token r // KV of the
    group, KV head r % KV. For query head h the entry is that token's number
    where the row is of h's own KV head, and a number no length reaches
    elsewhere (and in every column of a padded head): one comparison with
    the length left then masks the foreign heads and the dead positions."""
    import numpy as np

    col = np.arange(rows)[None, :]
    head = np.arange(Hp)[:, None]
    own = (col % kv_heads == head // (n_heads // kv_heads)) & (head < n_heads)
    return np.where(own, col // kv_heads, 2 ** 30).astype(np.int32)


def _group_pages(page_rows: int, max_blocks: int) -> int:
    return max(1, min(_GROUP_ROWS // page_rows, _MAX_GROUP_PAGES, max_blocks))


def paged_decode_attention(q, kc, vc, layer, tables, lengths, window=None):
    """The kernel: same arguments and result as `xla_decode_attention`.
    It is handed the whole pool and the layer's number, not the layer's
    slice: a slice of the pool as an operand is a copy of it (84 MB a layer
    for K and again for V at the benchmark's shapes)."""
    return _paged_call(q, kc, vc, layer, tables, lengths, window, None)


def paged_latent_attention(q, scale, pool, layer, tables, lengths, rank: int):
    """The kernel for latent attention in its absorbed form, the decode
    rows' twin of `chunk_latent_attention`: q [B, H, <= W] the absorbed
    queries, which score at `scale`; pool [L, NB, BS, 1, W], each cached
    row the key of all heads whose value is its first `rank` columns (a
    whole number of lanes); tables, lengths as `paged_decode_attention`'s.
    The pool is the kernel's one operand in HBM: a live page moves to VMEM
    once and serves as key and as value. -> [B, H, rank], bit for bit the
    first `rank` columns of `paged_decode_attention(q', pool, pool, ...)`
    on the query made here."""
    W = pool.shape[-1]
    # the kernel scales by its head width
    q = jnp.pad(q * jnp.asarray(scale * math.sqrt(W), q.dtype),
                ((0, 0), (0, 0), (0, W - q.shape[-1])))
    return _paged_call(q, pool, None, layer, tables, lengths, None, rank)


def _paged_call(q, kc, vc, layer, tables, lengths, window, value_dim):
    """The Pallas call of both entries: keys and values in two arrays, or
    (`vc` None) a pool whose rows hold their value in their first
    `value_dim` columns."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    L, NB, bs, kvh, _ = kc.shape
    max_blocks = tables.shape[1]
    page_rows = bs * kvh
    G = _group_pages(page_rows, max_blocks)
    rows = G * page_rows
    # query heads fill whole sublane tiles of the MXU's left operand; a
    # padded head belongs to no KV head and comes out 0
    tile = _sublane_tile(q.dtype)
    Hp = -(-H // tile) * tile
    qp = q if Hp == H else jnp.pad(q, ((0, 0), (0, Hp - H), (0, 0)))
    if window is None:
        lengths = jnp.clip(lengths, 0, max_blocks * bs)
    else:
        # the ring holds the window, and a page's turn comes once a slot
        assert window + bs <= max_blocks * bs, (window, bs, max_blocks)
    lengths = lengths.astype(jnp.int32)
    # for XLA's scheduler only: the live tokens are data, a quarter of the
    # tables' reach is a guess
    live = B * max_blocks * bs // 4

    kernel = functools.partial(
        _decode_kernel, scale=1.0 / math.sqrt(hd), group_pages=G,
        page_rows=page_rows, block_size=bs, max_blocks=max_blocks,
        window=window, value_dim=value_dim)
    vd = value_dim or hd
    pools = [kc] if vc is None else [kc, vc]
    o = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        out_shape=jax.ShapeDtypeStruct((B, Hp, vd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, Hp, hd), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((Hp, rows), lambda b, *_: (0, 0)),
            ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            out_specs=pl.BlockSpec((1, Hp, vd), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, rows, hd), x.dtype)
                            for x in pools] + [
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        # slots run in order: the buffers, their parity and the prefetch of
        # the next slot's first group are carried from one to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * Hp * kvh * live * (hd + vd)),
            bytes_accessed=int(len(pools) * live * kvh * hd * kc.dtype.itemsize
                               + 2 * q.size * q.dtype.itemsize),
            transcendentals=int(Hp * kvh * live),
        ),
        interpret=_INTERPRET,
    )(tables.reshape(-1).astype(jnp.int32), lengths,
      (jnp.asarray(layer, jnp.int32) * NB).reshape(1), qp,
      _column_tokens(Hp, rows, H, kvh),
      *(x.reshape(L * NB, page_rows, hd) for x in pools))
    return o if Hp == H else o[:, :H]


# ---------------------------------------------------------------------------
# which path a decode step takes
# ---------------------------------------------------------------------------


def decode_path(n_heads: int, n_kv_heads: int, head_dim: int,
                block_size: int, dtype):
    """(path, note) for a decode step of these shapes, decided once when
    the step is built: `KERNEL` on a TPU backend (or under the test
    interpreter) when the kernel takes the shape; otherwise `XLA`. `note`
    is None unless a TPU backend was refused the kernel: then it names
    every constraint the shape breaks, for the log and for `stats()`."""
    if jax.default_backend() != "tpu" and not _INTERPRET:
        return XLA, None
    tile = _sublane_tile(dtype)
    need = []
    if head_dim % 128:
        need.append(f"head_dim % 128 == 0 (head_dim={head_dim})")
    if block_size % tile:
        need.append(f"kv_block_size % {tile} == 0, the sublane tile of "
                    f"{jnp.dtype(dtype).name} (kv_block_size={block_size})")
    if n_heads % n_kv_heads:
        need.append(f"heads % kv_heads == 0 ({n_heads} % {n_kv_heads})")
    if need:
        return XLA, ("no paged-attention kernel for this shape, decode "
                     "attention gathers max_model_len positions a slot; the "
                     "kernel needs " + "; ".join(need))
    return KERNEL, None


def decode_attention(path: str, q, kc, vc, layer, tables, lengths,
                     window=None):
    fn = paged_decode_attention if path == KERNEL else xla_decode_attention
    return fn(q, kc, vc, layer, tables, lengths, window)

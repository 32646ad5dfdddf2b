"""ops/paged_attention.py: the decode kernel (in the Pallas interpreter)
against the XLA formulation it replaces, and the choice between them."""

import asyncio
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa

HD, BS = 128, 16


@pytest.fixture()
def interpreted(monkeypatch):
    """The kernel in the interpreter, two pages a group: a slot of eight
    blocks then takes up to four turns of the double buffer."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    monkeypatch.setattr(pa, "_group_pages", lambda rows, max_blocks: 2)


def pool_and_tables(rng, lens, *, kv, max_blocks, layers=2, dtype=jnp.bfloat16,
                    order="shuffled", hd=HD):
    """A pool whose layer 1 holds the slots' blocks (owned blocks drawn
    without order from 1..NB-1, block 0 the trash block) and whose every
    other block, and all of layer 0, is NaN: nothing but the live pages of
    the asked layer may be read."""
    need = [-(-n // BS) for n in lens]
    nb = sum(need) + 6
    ids = np.arange(1, nb)
    ids = rng.permutation(ids) if order == "shuffled" else ids[::-1]
    tables = np.zeros((len(lens), max_blocks), np.int32)
    used = 0
    for b, n in enumerate(need):
        tables[b, :n] = ids[used:used + n]
        used += n
    owned = np.zeros(nb, bool)
    owned[tables[tables > 0]] = True
    shape = (layers, nb, BS, kv, hd)
    k, v = (rng.standard_normal(shape).astype(np.float32) for _ in range(2))
    # a copy made here: x goes on, and on the CPU a float32 array may share
    # its buffer with the device's (the NaN then showed in `clean` now and
    # then: test_float32_cache, PR 29's run)
    clean = [jnp.asarray(x.copy(), dtype) for x in (k, v)]
    for x in (k, v):
        x[:-1] = np.nan
        x[-1, ~owned] = np.nan
    return clean, [jnp.asarray(x, dtype) for x in (k, v)], jnp.asarray(tables)


def check(lens, *, heads, kv, max_blocks=8, dtype=jnp.bfloat16,
          order="shuffled", seed=0):
    rng = np.random.default_rng(seed)
    (k, v), (k_nan, v_nan), tables = pool_and_tables(
        rng, lens, kv=kv, max_blocks=max_blocks, dtype=dtype, order=order)
    q = jnp.asarray(rng.standard_normal((len(lens), heads, HD)), dtype)
    lengths = jnp.asarray(lens, jnp.int32)
    layer = k.shape[0] - 1
    want = np.asarray(pa.xla_decode_attention(
        q, k, v, layer, tables, lengths), np.float32)
    got = np.asarray(jax.jit(pa.paged_decode_attention)(
        q, k_nan, v_nan, layer, tables, lengths), np.float32)
    assert np.isfinite(got).all()
    live = np.asarray(lens) > 0
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    # an inactive slot attends nothing: zeros, not the NaN of an empty softmax
    assert not got[~live].any()


# a pool of latents, each the key of all heads and in its first `rank`
# columns the value: (width in memory, rank, heads). 640 / 512 on 32 heads is
# the JoyAI and the Ling cell's; 256 / 128 on 8 pads the heads to a tile
LATENTS = {"latents_640_512": (640, 512, 32), "latents_256_128": (256, 128, 8)}
FORMS = ["two_arrays", *LATENTS]


def check_latents(lens, form, *, max_blocks=8, order="shuffled", seed=0):
    """`paged_latent_attention` against `ling.attend_latents` on the
    gathered context, on a pool whose every page that is not live is NaN;
    and bit for bit against the call it replaced, the pool handed to
    `paged_decode_attention` as keys and as values."""
    from ray_tpu.models import ling

    width, rank, heads = LATENTS[form]
    cfg = ling.LingConfig(kv_lora_rank=rank, n_heads=heads)
    assert cfg.latent_width == width and cfg.latent_dim < width
    rng = np.random.default_rng(seed)
    (pool, _), (pool_nan, _), tables = pool_and_tables(
        rng, lens, kv=1, max_blocks=max_blocks, order=order, hd=width)
    q = jnp.asarray(rng.standard_normal((len(lens), heads, cfg.latent_dim)),
                    jnp.bfloat16)
    lengths = jnp.asarray(lens, jnp.int32)
    layer = pool.shape[0] - 1
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    context = pool[layer][tables].reshape(len(lens), max_blocks * BS, width)
    want = np.asarray(ling.attend_latents(cfg, context, lengths)(q, scale),
                      np.float32)
    got = jax.jit(pa.paged_latent_attention, static_argnums=(1, 6))(
        q, scale, pool_nan, layer, tables, lengths, rank)
    assert got.shape == (len(lens), heads, rank)
    # the query as both step sets wrote it out before the entry held it
    qd = jnp.pad(q * jnp.asarray(scale * math.sqrt(width), q.dtype),
                 ((0, 0), (0, 0), (0, width - cfg.latent_dim)))
    two = jax.jit(pa.paged_decode_attention)(
        qd, pool_nan, pool_nan, layer, tables, lengths)[..., :rank]
    got, two = (np.asarray(x, np.float32) for x in (got, two))
    assert np.isfinite(got).all()
    assert (got == two).all()
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert not got[~live].any()


def check_form(form, lens, **kw):
    if form in LATENTS:
        check_latents(lens, form, **kw)
    else:
        check(lens, heads=8, kv=2, **kw)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("lens", [
    [40, 0, 17, 100],            # ragged, with an inactive slot (trash row)
    [1, BS - 1, BS, BS + 1],     # round a block's edge
    [8 * BS, 8 * BS - 1, 2 * BS, 2 * BS + 1],   # max_model_len, group edges
    [0, 0, 0, 0],                # nothing active at all
], ids=["ragged_inactive", "block_edge", "max_model_len", "all_inactive"])
def test_kernel_matches_xla_over_lengths(interpreted, lens, form):
    check_form(form, lens)


@pytest.mark.parametrize("form", FORMS)
@pytest.mark.parametrize("order", ["shuffled", "descending"])
def test_block_tables_neither_contiguous_nor_ordered(interpreted, order, form):
    check_form(form, [70, 33, 0, 128], order=order, seed=3)


@pytest.mark.parametrize("heads,kv", [(32, 8), (2, 1), (4, 4)],
                         ids=["gqa_4to1_8kv", "gqa_2to1_1kv", "mha"])
def test_grouped_query_heads(interpreted, heads, kv):
    """32/8 is the benchmark's cell; 2/1 pads the query heads to a sublane
    tile, and the padded heads belong to no KV head."""
    check([50, 5, 0, 97], heads=heads, kv=kv, seed=heads)


def test_float32_cache(interpreted):
    check([50, 5, 0, 97], heads=4, kv=2, dtype=jnp.float32)


def test_default_group_size_and_one_program_shape(monkeypatch):
    """The group size the chip runs (8 pages of 16 x 8 rows), and lengths
    as data: every mix of lengths runs the one compiled program."""
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert pa._group_pages(16 * 8, 128) == 8
    traces = []
    fn = jax.jit(lambda *a: traces.append(1) or pa.paged_decode_attention(*a))
    rng = np.random.default_rng(1)
    for lens in ([3, 200], [129, 0], [256, 256]):
        (k, v), _, tables = pool_and_tables(
            rng, [256, 256], kv=8, max_blocks=16, layers=1)
        q = jnp.asarray(rng.standard_normal((2, 32, HD)), jnp.bfloat16)
        lengths = jnp.asarray(lens, jnp.int32)
        got = np.asarray(fn(q, k, v, 0, tables, lengths), np.float32)
        want = np.asarray(pa.xla_decode_attention(
            q, k, v, 0, tables, lengths), np.float32)
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(got[live], want[live], atol=2e-2, rtol=2e-2)
    assert len(traces) == 1


def test_column_tokens_marks_own_head_and_padding():
    t = pa._column_tokens(16, 32, n_heads=4, kv_heads=2)   # rep 2
    assert t.shape == (16, 32)
    # head 0 and 1 read KV head 0 (even rows), head 2 and 3 KV head 1
    assert (t[0, 0::2] == np.arange(16)).all() and (t[0, 1::2] > 2 ** 20).all()
    assert (t[3, 1::2] == np.arange(16)).all() and (t[3, 0::2] > 2 ** 20).all()
    assert (t[4:] > 2 ** 20).all()                          # padded heads


def test_decode_path_follows_backend_and_shape(monkeypatch):
    bf16 = jnp.bfloat16
    assert pa.decode_path(32, 8, 128, 16, bf16) == (pa.XLA, None)   # the CPU
    monkeypatch.setattr(pa, "_INTERPRET", True)
    assert pa.decode_path(32, 8, 128, 16, bf16) == (pa.KERNEL, None)
    monkeypatch.setattr(pa, "_INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.decode_path(32, 8, 128, 16, bf16) == (pa.KERNEL, None)
    assert pa.decode_path(2, 1, 128, 8, jnp.float32) == (pa.KERNEL, None)
    for shape, word in (((32, 8, 64, 16, bf16), "head_dim"),
                        ((32, 8, 128, 8, bf16), "kv_block_size"),
                        ((32, 5, 128, 16, bf16), "kv_heads")):
        path, note = pa.decode_path(*shape)
        assert path == pa.XLA and word in note


def test_engine_on_a_tpu_says_when_it_is_refused_the_kernel(monkeypatch,
                                                            caplog):
    """A TPU backend with a shape the kernel does not take falls back to
    the XLA lines, and says so: once in the log and in stats()."""
    from ray_tpu.llm._engine import EngineConfig, PagedEngine
    from ray_tpu.models.llama import LlamaConfig, init_params

    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=2,
                      n_kv_heads=1, ffn_dim=64, max_seq_len=32,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level("WARNING", logger="ray_tpu.llm._engine"):
        eng = PagedEngine(cfg, params, EngineConfig(
            max_num_seqs=2, kv_block_size=8, num_kv_blocks=8, max_model_len=32))
    stats = eng.stats()
    assert stats["decode_attention"] == "xla"
    assert "head_dim" in stats["decode_attention_note"]
    assert [r for r in caplog.records if "head_dim" in r.getMessage()]


def test_engine_with_the_kernel_matches_dense_decode(monkeypatch):
    """PagedEngine built with the kernel (interpreter, head_dim 128) gives
    the tokens of the dense decoder, as test_paged_matches_dense_decode
    shows for the XLA path."""
    from ray_tpu.llm._engine import EngineConfig, PagedEngine
    from ray_tpu.llm._generate import generate
    from ray_tpu.models.llama import LlamaConfig, init_params

    monkeypatch.setattr(pa, "_INTERPRET", True)
    cfg = LlamaConfig(vocab_size=256, dim=256, n_layers=2, n_heads=2,
                      n_kv_heads=1, ffn_dim=256, max_seq_len=64,
                      dtype=jnp.float32, param_dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[1, 5, 9], [3, 3, 3, 7, 2, 8, 1, 4, 4, 6], [42]]
    dense = generate(cfg, params, prompts, max_new_tokens=8, temperature=0.0)
    eng = PagedEngine(cfg, params, EngineConfig(
        max_num_seqs=4, kv_block_size=8, num_kv_blocks=16, max_model_len=32))
    assert eng.stats()["decode_attention"] == "paged_kernel"

    async def run_one(p):
        return [t async for t in eng.generate_stream(
            p, max_tokens=8, temperature=0.0)]

    async def main():
        return await asyncio.gather(*[run_one(p) for p in prompts])

    assert asyncio.run(main()) == dense
    stats = eng.stats()
    assert stats["free_blocks"] == 16
    assert 0 < stats["attn_positions_live"] < stats["attn_positions_dense"]


# ---------------------------------------------------------------------------
# the attention of a prompt chunk that rides in a decode step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("start,n", [(0, 24), (0, 5), (16, 24), (37, 24),
                                     (100, 24), (104, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_attention_equals_dense_causal_attention(start, n, dtype):
    """24 chunk rows of one sequence at positions start.. over that
    sequence's table (tiles of two blocks, so the context takes several):
    each of the first n rows is dense causal attention over the keys at
    positions <= its own, whatever lies behind them in the table's blocks
    (NaN here: a tile past the chunk's end is never read, a key past a
    row's position never counted); the padding rows come out finite."""
    rng = np.random.default_rng(start + n)
    heads, kv, C = 4, 2, 24
    end = start + n
    (k, v), _, tables = pool_and_tables(rng, [8 * BS], kv=kv, max_blocks=8,
                                        dtype=jnp.float32)
    layer, row = k.shape[0] - 1, tables[0]
    live = -(-end // (2 * BS)) * 2 * BS            # whole tiles up to end
    pos = np.arange(8 * BS)
    flat = np.asarray(row)[pos // BS], pos % BS
    dead = jnp.asarray(pos >= live)[:, None, None]
    k_nan, v_nan = (
        x.at[layer, flat[0], flat[1]].set(
            jnp.where(dead, jnp.nan, x[layer, flat[0], flat[1]])).astype(dtype)
        for x in (k, v))
    q = jnp.asarray(rng.standard_normal((C, heads, HD)), dtype)
    qpos = start + jnp.arange(C, dtype=jnp.int32)
    got = np.asarray(jax.jit(pa.chunk_attention, static_argnames="tile")(
        q, k_nan, v_nan, layer, row, qpos, end, tile=2 * BS), np.float32)
    assert np.isfinite(got).all()
    keys, values = (np.repeat(np.asarray(x.astype(dtype), np.float32)[
        layer, flat[0], flat[1]], heads // kv, axis=1) for x in (k, v))
    s = np.einsum("chd,whd->hcw", np.asarray(q, np.float32), keys) / HD ** 0.5
    s = np.where(pos[None, None, :] <= np.asarray(qpos)[None, :, None],
                 s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("hcw,whd->chd", p / p.sum(-1, keepdims=True), values)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got[:n], want[:n], atol=tol, rtol=tol)


# --- a window over a ring -----------------------------------------------------


def ring_case(lens, window, ring_blocks, *, heads=8, kv=2, seed=0):
    """Slots whose last `window` positions lie in a ring of `ring_blocks`
    blocks each (position j at ring index j mod R), float32. The pages that
    hold a live position are finite (garbage where a position is dead), every
    other page of the pool is NaN: a page behind the window must not be
    fetched. -> (arguments with the NaN pool, with a clean one, the dense
    softmax over the live positions)."""
    rng = np.random.default_rng(seed)
    B, R = len(lens), ring_blocks * BS
    T = max(max(lens), 1)
    K, V = (rng.standard_normal((B, T, kv, HD)).astype(np.float32)
            for _ in range(2))
    q = rng.standard_normal((B, heads, HD)).astype(np.float32)
    pool_k = np.full((2, B * ring_blocks, BS, kv, HD), np.nan, np.float32)
    pool_v = pool_k.copy()
    want = np.zeros((B, heads, HD), np.float32)
    for b, n in enumerate(lens):
        lo = max(0, n - window)
        for j in range(lo, n):
            pool_k[1, b * ring_blocks + (j % R) // BS] = 7.0
            pool_v[1, b * ring_blocks + (j % R) // BS] = -5.0
        for j in range(lo, n):
            pool_k[1, b * ring_blocks + (j % R) // BS, j % BS] = K[b, j]
            pool_v[1, b * ring_blocks + (j % R) // BS, j % BS] = V[b, j]
        for h in range(heads if n else 0):
            g = h // (heads // kv)
            s = K[b, lo:n, g] @ q[b, h] / np.sqrt(HD)
            p = np.exp(s - s.max())
            want[b, h] = (p / p.sum()) @ V[b, lo:n, g]
    tables = (np.arange(B)[:, None] * ring_blocks
              + np.arange(ring_blocks)[None]).astype(np.int32)
    rest = (1, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))
    nan = (jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v)) + rest
    clean = (jnp.asarray(q), jnp.asarray(np.nan_to_num(pool_k)),
             jnp.asarray(np.nan_to_num(pool_v))) + rest
    return nan, clean, want


@pytest.mark.parametrize("lens,window,ring_blocks,heads,kv", [
    ([5, 0, 40, 100], 32, 4, 8, 2),       # inside, inactive, across, wrapped
    ([33, 64, 65, 1000], 32, 4, 8, 2),    # the ring's end, many wraps
    ([31, 32, 47, 48], 32, 3, 8, 2),      # a ring of window + one block
    ([200, 17, 16, 15], 40, 4, 2, 1),     # a window of no whole blocks
    ([9, 130, 1, 77], 24, 3, 32, 4),      # the cell's 32 heads on 4
], ids=["mixed", "wraps", "tight_ring", "ragged_window", "gqa_8to1"])
def test_a_window_reads_the_ring_and_no_page_behind_it(
        interpreted, lens, window, ring_blocks, heads, kv):
    """The kernel in the interpreter, its XLA twin and the dense softmax
    over the last `window` positions agree, on a pool whose pages behind the
    window are NaN for the kernel."""
    nan, clean, want = ring_case(lens, window, ring_blocks, heads=heads, kv=kv)
    twin = np.asarray(pa.xla_decode_attention(*clean, window))
    np.testing.assert_allclose(twin, want, atol=2e-5)
    got = np.asarray(jax.jit(pa.paged_decode_attention, static_argnums=(6,))(
        *nan, window))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert not got[np.asarray(lens) == 0].any()


def test_a_window_longer_than_every_sequence_is_no_window(interpreted):
    """A table as long as the sequences is a ring that never wraps."""
    rng = np.random.default_rng(5)
    lens = [40, 0, 17, 100]
    (k, v), _, tables = pool_and_tables(rng, lens, kv=2, max_blocks=8,
                                        dtype=jnp.float32)
    q = jnp.asarray(rng.standard_normal((4, 8, HD)), jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    live = np.asarray(lens) > 0
    plain = np.asarray(pa.xla_decode_attention(q, k, v, 1, tables, lengths))
    for fn in (pa.xla_decode_attention, jax.jit(
            pa.paged_decode_attention, static_argnums=(6,))):
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v, 1, tables, lengths, 112))[live],
            plain[live], atol=2e-5)
    # and a window of 30 on the same pool reads the last 30 alone
    short = np.asarray(pa.xla_decode_attention(q, k, v, 1, tables, lengths, 30))
    kern = np.asarray(jax.jit(pa.paged_decode_attention, static_argnums=(6,))(
        q, k, v, 1, tables, lengths, 30))
    np.testing.assert_allclose(kern[live], short[live], atol=2e-5)
    assert np.abs(short[0] - plain[0]).max() > 1e-3      # 40 > 30
    np.testing.assert_allclose(short[2], plain[2], atol=2e-5)   # 17 < 30


@pytest.mark.parametrize("start,n", [(0, 20), (30, 32), (50, 7), (200, 32)])
def test_a_chunks_rows_see_their_window_of_the_ring(start, n):
    """`chunk_attention` with a window over a ring of window + C positions,
    the chunk's keys written first: row i sees start + i - window < j <=
    start + i, whatever the ring's wrap; padded rows write nothing."""
    C, window, kv, heads = 32, 24, 2, 8
    ring_blocks = -(-(window + C) // BS)
    R = ring_blocks * BS
    rng = np.random.default_rng(start)
    T = start + n
    K, V = (rng.standard_normal((T, kv, HD)).astype(np.float32)
            for _ in range(2))
    q = rng.standard_normal((C, heads, HD)).astype(np.float32)
    pool_k = rng.standard_normal((1, ring_blocks, BS, kv, HD)).astype(np.float32)
    pool_v = pool_k.copy()
    for j in range(max(0, T - R), T):
        pool_k[0, (j % R) // BS, j % BS] = K[j]
        pool_v[0, (j % R) // BS, j % BS] = V[j]
    qpos = start + np.arange(C)
    got = np.asarray(pa.chunk_attention(
        jnp.asarray(q), jnp.asarray(pool_k), jnp.asarray(pool_v), 0,
        jnp.arange(ring_blocks, dtype=jnp.int32), jnp.asarray(qpos, jnp.int32),
        jnp.int32(T), tile=2 * BS, window=window))
    for i in range(n):
        lo = max(0, qpos[i] - window + 1)
        for h in range(heads):
            g = h // (heads // kv)
            s = K[lo:qpos[i] + 1, g] @ q[i, h] / np.sqrt(HD)
            p = np.exp(s - s.max())
            np.testing.assert_allclose(
                got[i, h], (p / p.sum()) @ V[lo:qpos[i] + 1, g], atol=2e-5)


def kernel_equations(window):
    from tests.test_v5e_compile import equations, pallas_programs

    spec = jax.ShapeDtypeStruct
    traced = jax.make_jaxpr(
        lambda q, k, v, t, l: pa.paged_decode_attention(q, k, v, 1, t, l, window)
    )(spec((4, 32, HD), jnp.bfloat16), spec((2, 64, 32, 4, HD), jnp.bfloat16),
      spec((2, 64, 32, 4, HD), jnp.bfloat16), spec((4, 40), jnp.int32),
      spec((4,), jnp.int32))
    (program,) = pallas_programs(traced.jaxpr, "paged_decode_attention")
    return equations(program)


def latent_program(pool_once: bool):
    """The kernel's traced program at the JoyAI cell's shapes (16 rows, 32
    heads, a pool of 40 x 6,145 blocks of 16 latents 640 wide, tables of
    1,024), and the arrays the call is handed: through the latent entry, or
    the pool as keys and as values as both step sets called it before."""
    spec = jax.ShapeDtypeStruct
    if pool_once:
        def fn(q, pool, t, l):
            return pa.paged_latent_attention(
                q[..., :576], 192 ** -0.5, pool, 1, t, l, 512)
    else:
        def fn(q, pool, t, l):
            return pa.paged_decode_attention(q, pool, pool, 1, t, l)[..., :512]
    traced = jax.make_jaxpr(fn)(
        spec((16, 32, 640), jnp.bfloat16),
        spec((40, 6145, 16, 1, 640), jnp.bfloat16),
        spec((16, 1024), jnp.int32), spec((16,), jnp.int32))
    (call,) = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_decode_attention"
    return (call.params["jaxpr"], [v.aval.shape for v in call.invars],
            call.outvars[0].aval)


def test_a_pool_that_is_its_own_value_is_one_operand_and_a_smaller_body():
    """One body here too: with keys and values in two arrays it is the
    program it was, and over a pool of latents it holds one operand in HBM,
    one buffer, one copy and one wait a page, which is fewer equations than
    the two-operand body both latent families ran (815)."""
    from tests.test_v5e_compile import equations

    assert kernel_equations(None) == KERNEL_EQUATIONS
    pages = (40 * 6145, 16, 640)
    twice, shapes, out = latent_program(pool_once=False)
    assert shapes.count(pages) == 2 and out.shape == (16, 32, 640)
    once, shapes, out = latent_program(pool_once=True)
    assert shapes.count(pages) == 1 and out.shape == (16, 32, 512)
    assert equations(once) <= LATENT_EQUATIONS < equations(twice)


def test_the_window_costs_callers_without_one_nothing():
    """One body: without a window it is the program it was (455 equations at
    eight pages a group, counted on the tree before the window existed), and
    the window is a few scalar operations a page and one comparison a group
    more, not a second body beside it."""
    assert kernel_equations(None) == KERNEL_EQUATIONS
    assert KERNEL_EQUATIONS < kernel_equations(1024) <= KERNEL_EQUATIONS + 80


KERNEL_EQUATIONS = 455
# the latent body at the JoyAI cell's sixteen pages a group (PR 61)
LATENT_EQUATIONS = 749

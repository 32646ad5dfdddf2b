"""Compiles for a described (not attached) TPU v5e, so that what only the
chip's compiler shows is checked in tier-1 at no chip time. Keep every such
test in this one file: the worker that runs it loads the TPU's library and
holds it until it exits."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops import grouped_ffn as gf
from ray_tpu.ops import kda
from ray_tpu.ops import paged_attention as pa

PALLAS = 'custom_call_target="tpu_custom_call"'
B, H, KVH, S, HD = 1, 16, 8, 1024, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def on_v5e(topo, monkeypatch):
    """Shapes placed on the described chip; the kernels' own path (the
    module asks the live backend, which is the CPU here); no compile cache
    (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    monkeypatch.setattr(fa, "_kernel_path", lambda *a: True)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def pallas_calls(hlo: str):
    """{instruction name: (operand shapes, result shapes)} of the Pallas
    custom calls in a compiled module's text, operands resolved through
    the instructions that define them."""
    shape = re.compile(r"(?:bf16|f32|s32|u32)\[[\d,]*\]")
    defs = {m.group(1): m.group(2) for m in re.finditer(
        r"%(\S+) = (.*?) [a-z][a-z\-]*\(", hlo)}
    out = {}
    for line in hlo.splitlines():
        if PALLAS not in line:
            continue
        m = re.search(r"%(\S+) = (.*?) custom-call\((.*?)\), custom_call_target",
                      line)
        operands = [shape.findall(defs[o]) for o in re.findall(
            r"%([^\s,)]+)", m.group(3))]
        out[m.group(1)] = ([s for o in operands for s in o],
                           shape.findall(m.group(2)))
    return out


def latent_attention_calls(hlo: str):
    """[(operand shapes, result shapes)] of the decode kernel's calls."""
    return [shapes for name, shapes in pallas_calls(hlo).items()
            if name.startswith("paged_decode_attention")]


def grouped_ffn_calls(hlo: str):
    """[(operand shapes, result shapes)] of the held experts' kernel."""
    return [shapes for name, shapes in pallas_calls(hlo).items()
            if name.startswith("grouped_ffn")]


def assert_the_chunk_pass_is_the_kernel(hlo: str, calls: int, rows: int,
                                        heads: int):
    """The delta rule's chunk pass in a compiled program: one call of
    `kda_chunk` a KDA layer over all its rows, from the rows as the layer's
    matmuls leave them, and nothing of `kda_chunked_xla`: no triangular
    solve, no `[heads, C, C, 128]` tensor of decays in HBM, no loop over
    chunks."""
    results = [res for name, (_, res) in pallas_calls(hlo).items()
               if name.startswith("kda_chunk")]
    assert results == [[f"f32[{rows},{heads},128]",
                        f"f32[{heads},128,128]"]] * calls
    assert "triangular-solve" not in hlo and "TriangularSolve" not in hlo
    assert not re.findall(r"f32\[(?:\d+,)?%d,(\d+),\1,128\]" % heads, hlo)
    assert not re.search(r'while\([^\n]*op_name="[^"]*/kda/', hlo)


def sub_jaxprs(eqn):
    for value in eqn.params.values():
        for sub in (value if isinstance(value, (list, tuple)) else [value]):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def equations(jaxpr) -> int:
    """Equations of a traced program, those of its loops' and branches'
    bodies counted once each."""
    return sum(1 + sum(equations(sub) for sub in sub_jaxprs(eqn))
               for eqn in jaxpr.eqns)


def pallas_programs(jaxpr, name: str):
    """The programs of the `pallas_call`s named `name` under a traced
    program."""
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == name):
            yield eqn.params["jaxpr"]
        for sub in sub_jaxprs(eqn):
            yield from pallas_programs(sub, name)


def assert_one_lowering_of_the_chunk_pass(text: str, calls: int):
    """A program's KDA layers share one lowering of the kernel: one private
    function that holds the one custom call, called once a layer."""
    assert text.count("func.func private @_kda_chunk_kernel(") == 1
    assert len(re.findall(r"call @_kda_chunk_kernel\(", text)) == calls
    assert text.count('kernel_name = "kda_chunk"') == 1


# what `kda_chunk`'s traced program holds (PR 50's held 2,993, and cost every
# program that holds it ~2.9 s of a run's set-up, cache or no cache): a count,
# which no machine's load moves. One that grows past this is a finding.
KDA_CHUNK_EQUATIONS = 750


@pytest.mark.parametrize("heads,rows", [(32, 512), (64, 256)])
def test_the_chunk_pass_is_a_small_program(heads, rows):
    """The size pin: whatever the rows and heads, the kernel's body is
    traced for one turn of heads and one chunk, and stays small enough to
    trace and lower in a fraction of a second a program."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    traced = jax.make_jaxpr(kda._kda_chunk_kernel)(
        *[f32(rows, heads, 128)] * 4, f32(rows, heads), f32(heads, 128, 128))
    (program,) = pallas_programs(traced.jaxpr, "kda_chunk")
    assert equations(program) <= KDA_CHUNK_EQUATIONS


def ling_cell():
    """The Ling cell's model and engine shapes (published widths, 7 layers,
    128 held experts, 64 slots, 16,384 blocks)."""
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import ling

    return (ling.LingConfig(
        vocab_size=39296, n_layers=7, layer_ids=(1, 6, 7, 8, 9, 10, 11),
        first_k_dense=1, n_held=128, max_seq_len=4096),
        EngineConfig(max_num_seqs=64, kv_block_size=16,
                     num_kv_blocks=16384, max_model_len=4096))


def attention_grad(spec):
    q, k, v = spec((B, H, S, HD)), spec((B, KVH, S, HD)), spec((B, KVH, S, HD))

    def loss(q, k, v):
        return fa.flash_attention_bhsd(q, k, v, causal=True).astype(
            jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), (q, k, v)


def chunk(spec):
    f32 = jnp.float32
    args = (spec((B, H, S, HD)), spec((B, KVH, S, HD)), spec((B, KVH, S, HD)),
            spec((B, H, S, HD), f32), spec((B, H, S, 1), f32),
            spec((B, H, S, 1), f32))
    return (lambda *a: fa.flash_chunk_bhsd(*a, causal=True)), args


def hop_backward(spec):
    f32 = jnp.float32
    args = (spec((B, H, S, HD)), spec((B, KVH, S, HD)), spec((B, KVH, S, HD)),
            spec((B, H, S, HD)), spec((B, H, S, 1), f32),
            spec((B, H, S, 1), f32))
    return (lambda *a: fa.flash_hop_bwd(*a, True)), args


Q, KV = f"bf16[{B},{H},{S},{HD}]", f"bf16[{B},{KVH},{S},{HD}]"
QF, ROW, COL = (f"f32[{B},{H},{S},{HD}]", f"f32[{B},{H},{S},1]",
                f"f32[{B},{H},1,{S}]")


@pytest.mark.parametrize("build,expected", [
    # name -> (operands, results): the signatures benchmark/lib/xplane.py's
    # flash_call_shape tells the kernels apart by (q, k, v first; 3 -> 2
    # forward, 6 -> 1 dq, 6 -> 2 dkv)
    (attention_grad, {
        "flash_fwd": ([Q, KV, KV], [Q, ROW]),
        "flash_dq": ([Q, KV, KV, Q, ROW, ROW], [Q]),
        "flash_dkv": ([Q, KV, KV, Q, COL, COL], [QF, QF])}),
    (chunk, {"flash_chunk": ([Q, KV, KV, QF, ROW, ROW], [QF, ROW, ROW])}),
    (hop_backward, {
        "flash_hop_dq": ([Q, KV, KV, Q, ROW, ROW], [QF]),
        "flash_hop_dkv": ([Q, KV, KV, Q, COL, COL], [QF, QF])}),
], ids=["attention_grad", "chunk", "hop_backward"])
def test_flash_kernels_compile_for_v5e_under_their_names(on_v5e, build,
                                                         expected):
    """Each `pl.pallas_call` of ops/flash_attention.py is a named Mosaic
    custom call in the v5e program, with the operands and results it had
    before it was named: the trace's readers recognise the calls by
    signature and the breakdown shows them by name."""
    fn, args = build(on_v5e)
    hlo = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    calls = pallas_calls(hlo)
    assert len(calls) == len(expected), sorted(calls)
    for name, signature in expected.items():
        found = [sig for instr, sig in calls.items() if name + "_" in instr
                 or instr.startswith(name + ".") or instr == name]
        assert found == [signature], (name, calls)


# 15.75 GiB written as GB: 1.16e9 under the chip's own limit
# (`V5E_BYTES_LIMIT` below), so the tests held to it are held the tighter
V5E_HBM_BYTES = 15.75e9


def mistral_decode_step(on_v5e, monkeypatch, blocks, width):
    """`jit_paged_decode_step` compiled for the described v5e at the serve
    cells' shapes (Mistral-7B widths, 16 layers, 32 slots, blocks of 16,
    tables of 128) with a pool of `blocks` and a prompt chunk of `width`
    rows (0: none)."""
    from ray_tpu.llm._engine import LLAMA_STEPS, EngineConfig
    from ray_tpu.models.llama import LlamaConfig, init_params

    monkeypatch.setattr(pa, "decode_path", lambda *a: (pa.KERNEL, None))
    cfg = LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=16, n_heads=32, n_kv_heads=8,
        ffn_dim=14336, rope_theta=1e6, max_seq_len=2048, dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    slots = 32
    step, path, note = LLAMA_STEPS.make_decode_step(cfg, EngineConfig(
        max_num_seqs=slots, kv_block_size=16, num_kv_blocks=blocks,
        max_model_len=2048))
    assert (path, note) == (pa.KERNEL, None)
    # the tree the engine hands the step: Q, K and V packed into one leaf
    params = jax.tree.map(
        lambda x: on_v5e(x.shape, x.dtype),
        jax.eval_shape(lambda: LLAMA_STEPS.step_params(
            cfg, init_params(cfg, jax.random.PRNGKey(0)))))
    pool = on_v5e((16, blocks + 1, 16, 8, 128))
    chunk = (on_v5e((width,), jnp.int32), on_v5e((3,), jnp.int32))
    return step.trace(
        width, params, pool, pool, on_v5e((slots, 128), jnp.int32),
        on_v5e((slots,), jnp.int32), on_v5e((slots,), jnp.bool_),
        on_v5e((slots,), jnp.int32), on_v5e((slots, 2), jnp.uint32),
        on_v5e((slots,), jnp.float32), on_v5e((slots + 3,), jnp.int32),
        on_v5e((slots,), jnp.int32), *(chunk if width else ()),
    ).lower(lowering_platforms=("tpu",)).compile()


# an instruction of its own (a fusion's result, a copy) the size of one
# layer's attention input weight: a slice of the stack staged in a buffer, or
# a transposed copy of it. A `dynamic-slice` inside a matmul's fusion is the
# weight streaming from the stack and is not one.
STAGED_WEIGHT = re.compile(
    r"= bf16\[1,4096,(?:4096|1024|6144)\]\S* (?:fusion|copy)\(")


def assert_qkv_is_one_matmul_over_the_stack(hlo: str, rows: int):
    """Q, K and V of a layer come out of one fusion `bf16[1, rows, 6144]`
    that reads `wqkv [16, 4096, 6144]` from the stack in place, and no
    layer's weight is staged or copied on its way to a matmul (as `wq`,
    `wk` and `wv` each were, sliced, transposed and then used: PR 35)."""
    assert not STAGED_WEIGHT.findall(hlo)
    qkv = re.findall(
        rf"%(\S+) = bf16\[1,{rows},6144\]\S* fusion\(.*kind=kOutput, "
        r"calls=%(\S+?),", hlo)
    assert len(qkv) == 1, qkv
    # the fusion's own computation: the whole stack is its operand, the dot
    # is inside
    operands, body = hlo.split(f"%{qkv[0][1]} (", 1)[1].split(
        "\n}", 1)[0].split("\n", 1)
    assert "bf16[16,4096,6144]" in operands and " convolution(" in body


def test_decode_step_compiles_for_v5e_with_the_paged_kernel(on_v5e,
                                                            monkeypatch):
    """The step without a chunk, 2,560 blocks: the attention is the named
    Pallas call, no value over all 2,048 positions of every slot is left,
    the pool is not copied, and the program fits the chip."""
    blocks = 2560
    compiled = mistral_decode_step(on_v5e, monkeypatch, blocks, 0)
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    kernels = [line for line in hlo.splitlines() if PALLAS in line]
    assert len(kernels) == 1 and "%paged_decode_attention" in kernels[0]
    assert f"bf16[{16 * (blocks + 1)},128,128]" in kernels[0]   # pool in place
    assert not re.findall(r"(?:f32|bf16)\[32,2048,[\d,]*\]", hlo)
    assert_qkv_is_one_matmul_over_the_stack(hlo, 32)
    m = compiled.memory_analysis()
    # the pool (2.7 GB) rides in the scan's carry and is donated: no copy
    assert m.alias_size_in_bytes > 2.6e9 and m.temp_size_in_bytes < 0.2e9
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM_BYTES


@pytest.mark.parametrize("blocks", [2560, 5120])
def test_decode_step_with_the_widest_chunk_compiles_for_v5e_pool_in_place(
        on_v5e, monkeypatch, blocks):
    """The step that carries a prompt chunk of the ladder's widest width, at
    the cells' 2,560 blocks and at 5,120 (which the whole-prompt prefills,
    with their second copy of the pool, did not fit: 18.4 GB): the chunk's
    keys and values are scattered into the donated pool and read from it in
    place (no second buffer of the pool's shape, temporaries under 1 GB),
    the decode rows keep the paged kernel, and the program fits the chip."""
    from ray_tpu.llm._engine import EngineConfig, chunk_ladder

    width = chunk_ladder(EngineConfig(max_model_len=2048))[-1]
    assert width == 256
    compiled = mistral_decode_step(on_v5e, monkeypatch, blocks, width)
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    kernels = [line for line in hlo.splitlines() if PALLAS in line]
    assert len(kernels) == 1 and "%paged_decode_attention" in kernels[0]
    pool = f"bf16[16,{blocks + 1},16,8,128]"
    assert not re.findall(
        r" (?:copy|dynamic-slice)\([^)]*" + re.escape(pool), hlo)
    m = compiled.memory_analysis()
    pool_bytes = 2 * 16 * (blocks + 1) * 16 * 8 * 128 * 2
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 1e9
    # beside the step's tree the engine keeps the leaves it was given, wq,
    # wk and wv, under their names (`engine.params`): 0.8 GB more
    kept = 16 * 4096 * 6144 * 2
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + kept) < V5E_HBM_BYTES
    assert_qkv_is_one_matmul_over_the_stack(hlo, 32 + width)


def test_decode_step_with_the_narrower_chunk_projects_qkv_in_one_matmul(
        on_v5e, monkeypatch):
    """The ladder's other width: the third program the cells run."""
    from ray_tpu.llm._engine import EngineConfig, chunk_ladder

    width = chunk_ladder(EngineConfig(max_model_len=2048))[0]
    assert width == 128
    hlo = mistral_decode_step(on_v5e, monkeypatch, 2560, width).as_text()
    assert_qkv_is_one_matmul_over_the_stack(hlo, 32 + width)


def test_ling_decode_step_compiles_for_v5e_without_copying_its_caches(
        on_v5e, monkeypatch):
    """`jit_paged_decode_step` of the Ling family at the cell's shapes
    (published widths, 7 layers, 128 held experts, 64 slots, 16,384 blocks):
    the latent attention is the paged kernel over the pool in place (a latent
    640 wide in memory: at 576 the compiler laid the pool out blocks-innermost
    and copied it there and back every step, PR 29), the recurrent state and
    the pool are donated and not copied, an expert layer's held experts are
    one call of the grouped kernel (no grouped-matmul custom call of XLA's
    and no `conditional` around pieces of the rows is left), and the program
    fits the chip beside 10.35 GB of weights."""
    from ray_tpu.llm import _ling_steps
    from ray_tpu.models import ling

    monkeypatch.setattr(pa, "decode_path", lambda *a: (pa.KERNEL, None))
    monkeypatch.setattr(gf, "ffn_path", lambda *a: gf.KERNEL)
    cfg, ecfg = ling_cell()
    step, path, note = _ling_steps.make_decode_step(cfg, ecfg)
    assert (path, note) == (pa.KERNEL, None)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: ling.init_params(cfg, jax.random.PRNGKey(0))))
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _ling_steps.alloc_cache(cfg, ecfg))]
    compiled = step.trace(
        params, *caches, on_v5e((64, 256), jnp.int32),
        on_v5e((64,), jnp.int32), on_v5e((64,), jnp.bool_),
        on_v5e((64,), jnp.int32), on_v5e((64, 2), jnp.uint32),
        on_v5e((64,), jnp.float32),
        on_v5e((64 + len(_ling_steps.COUNTERS),), jnp.int32),
        on_v5e((64,), jnp.int32), on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    # the pool is the kernel's one operand in HBM (PR 61): its value is not
    # a second one, and what comes back is as wide as the value
    (ops, res), = latent_attention_calls(hlo)
    assert ops.count("bf16[16385,16,640]") == 1
    assert res == ["bf16[64,32,512]"]
    experts = grouped_ffn_calls(hlo)
    assert len(experts) == cfg.moe_layers == 6
    assert all(res == ["bf16[512,2560]"] and "bf16[128,2560,768]" in ops
               for ops, res in experts)
    assert "%ragged-dot-none" not in hlo and " conditional(" not in hlo
    # neither the pool nor the state is copied, whole or by layer
    assert not re.findall(r" copy\([^)]*(?:16385|64,32,128,128)", hlo)
    m = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.2e9
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM_BYTES


def test_ling_prefill_of_2048_tokens_compiles_for_v5e_with_the_grouped_kernel(
        on_v5e, monkeypatch):
    """`jit_paged_prefill` of the Ling family at the cell's widest bucket
    (S = 2,048: 16,384 pairs a layer, the grouped kernel's widest call): the
    kernel's blocks fit the chip's VMEM as the chip's compiler counts them,
    every expert layer is one call, and the program fits beside the
    weights."""
    from ray_tpu.llm import _ling_steps
    from ray_tpu.models import ling

    monkeypatch.setattr(gf, "ffn_path", lambda *a: gf.KERNEL)
    monkeypatch.setattr(kda, "chunk_path", lambda *a: kda.KERNEL)
    cfg, ecfg = ling_cell()
    prefill = _ling_steps.make_prefill(cfg, ecfg)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: ling.init_params(cfg, jax.random.PRNGKey(0))))
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _ling_steps.alloc_cache(cfg, ecfg))]
    S = 2048
    lowered = prefill.trace(
        S, params, *caches, on_v5e((256,), jnp.int32),
        on_v5e((S,), jnp.int32), on_v5e((), jnp.int32),
        on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",))
    assert_one_lowering_of_the_chunk_pass(lowered.as_text(), 6)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_prefill")
    experts = grouped_ffn_calls(hlo)
    assert len(experts) == 6
    assert all(res == [f"bf16[{S * cfg.top_k},2560]"] for _, res in experts)
    assert "%ragged-dot-none" not in hlo
    assert_the_chunk_pass_is_the_kernel(hlo, 6, S, 32)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM_BYTES
    assert m.temp_size_in_bytes <= 0.541e9  # what the XLA form's prefill took


def test_solar_decode_step_with_the_widest_chunk_compiles_for_v5e_in_place(
        on_v5e, monkeypatch):
    """`jit_paged_decode_step` of the Solar-Open2 family at the cell's shapes
    (published widths, one period of 4 layers, 40 held experts of 320, 16
    slots, 32,768 blocks, 64 state snapshots) with the widest chunk: the
    decode rows' attention is the paged kernel at 64 query heads on 8 KV
    heads over the pool in place, the pool, the slots' state and the
    snapshot pool are donated and not copied, a layer's held experts are one
    call of the grouped kernel over the chunk's and the slots' 2,176 pairs,
    and the program fits the chip beside 6.6 GB of weights."""
    from ray_tpu.llm import _solar_steps
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import solar

    monkeypatch.setattr(pa, "decode_path", lambda *a: (pa.KERNEL, None))
    monkeypatch.setattr(gf, "ffn_path", lambda *a: gf.KERNEL)
    monkeypatch.setattr(kda, "chunk_path", lambda *a: kda.KERNEL)
    cfg = solar.SolarConfig(
        vocab_size=24576, n_layers=4, layer_ids=(4, 5, 6, 7), n_held=40,
        max_seq_len=17408)
    ecfg = EngineConfig(max_num_seqs=16, kv_block_size=16,
                        num_kv_blocks=32768, max_model_len=17408,
                        prefix_cache=True, num_state_snapshots=64)
    assert cfg.kinds() == ["gqa", "kda", "kda", "kda"]
    C = _solar_steps.chunk_ladder(ecfg)[-1]
    assert C == 256
    step, path, note = _solar_steps.make_decode_step(cfg, ecfg)
    assert (path, note) == (pa.KERNEL, None)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: solar.init_params(cfg, jax.random.PRNGKey(0))))
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _solar_steps.alloc_cache(cfg, ecfg))]
    B = 16
    lowered = step.trace(
        C, params, *caches, on_v5e((B, 1088), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((B,), jnp.bool_),
        on_v5e((B,), jnp.int32), on_v5e((B, 2), jnp.uint32),
        on_v5e((B,), jnp.float32),
        on_v5e((B + len(_solar_steps.COUNTERS) + 3,), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((C,), jnp.int32),
        on_v5e((6,), jnp.int32), on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",))
    assert_one_lowering_of_the_chunk_pass(lowered.as_text(), 3)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    kernels = [line for line in hlo.splitlines()
               if PALLAS in line and "%paged_decode_attention" in line]
    assert len(kernels) == 1 and "bf16[32769,128,128]" in kernels[0]
    experts = grouped_ffn_calls(hlo)
    assert len(experts) == cfg.n_layers == 4
    assert all(res == ["bf16[2176,4096]"] and "bf16[40,4096,1280]" in ops
               for ops, res in experts)
    assert "%ragged-dot-none" not in hlo
    assert_the_chunk_pass_is_the_kernel(hlo, 3, C, 64)
    # neither pool nor the slots' state is copied whole
    assert not re.findall(
        r" copy\([^)]*(?:32769|3,16,64,128,128|65,3,64,128,128)", hlo)
    m = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    assert 6.5e9 < weight_bytes < 6.8e9
    assert m.alias_size_in_bytes >= cache_bytes
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM_BYTES
    assert m.temp_size_in_bytes <= 0.34e9   # what the XLA form's step took
    print("solar step: weights %.3f GB caches %.3f GB temp %.3f GB" % (
        weight_bytes / 1e9, cache_bytes / 1e9, m.temp_size_in_bytes / 1e9))


def test_brumby_decode_step_with_the_widest_chunk_compiles_for_v5e_in_place(
        on_v5e, monkeypatch):
    """`jit_paged_decode_step` of the Brumby family at the cell's shapes
    (published widths, six layers, the whole vocabulary, 16 slots, 8 state
    snapshots and the trash entry) with the widest chunk: the decode rows'
    retention is the Pallas kernel `retention_step` over every slot's state
    in place, no cache array has a block axis, the states and the snapshot
    pool are donated and not copied, and the program fits the chip beside
    7.1 GB of weights."""
    from ray_tpu.llm import _brumby_steps
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import brumby
    from ray_tpu.ops import power_retention as pr

    monkeypatch.setattr(pr, "step_path", lambda: pr.KERNEL)
    cfg = brumby.BrumbyConfig.brumby_14b(n_layers=6)
    ecfg = EngineConfig(max_num_seqs=16, kv_block_size=16,
                        num_kv_blocks=65536, max_model_len=32768,
                        prefix_cache=True, num_state_snapshots=8)
    C = _brumby_steps.chunk_ladder(ecfg)[-1]
    assert C == 256
    step, path, note = _brumby_steps.make_decode_step(cfg, ecfg)
    assert (path, note) == (pr.KERNEL, None)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: brumby.init_params(cfg, jax.random.PRNGKey(0))))
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _brumby_steps.alloc_cache(cfg, ecfg))]
    assert [c.shape for c in caches] == [
        (6, 17, 8, 8704, 128), (6, 17, 8, 128, 128),
        (9, 6, 8, 8704, 128), (9, 6, 8, 128, 128)]
    B = 16
    compiled = step.trace(
        C, params, *caches, on_v5e((B, 2048), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((B,), jnp.bool_),
        on_v5e((B,), jnp.int32), on_v5e((B, 2), jnp.uint32),
        on_v5e((B,), jnp.float32),
        on_v5e((B + len(_brumby_steps.COUNTERS) + 3,), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((C,), jnp.int32),
        on_v5e((6,), jnp.int32), on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    kernels = [line for line in hlo.splitlines()
               if PALLAS in line and "%retention_step" in line]
    assert len(kernels) == 1 and "f32[6,17,8,8704,128]" in kernels[0]
    # neither the slots' states nor the snapshot pool is copied whole
    assert not re.findall(r" copy\([^)]*(?:6,17,8,8704|9,6,8,8704)", hlo)
    m = compiled.memory_analysis()
    cache_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    assert 7.0e9 < weight_bytes < 7.2e9 and 5.5e9 < cache_bytes < 5.7e9
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < 0.3e9
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_HBM_BYTES
    print("brumby step: weights %.3f GB caches %.3f GB temp %.3f GB" % (
        weight_bytes / 1e9, cache_bytes / 1e9, m.temp_size_in_bytes / 1e9))


# the chip's own `bytes_limit` (PERF.md section 7, PR 44), in bytes: what
# `V5E_HBM_BYTES` above rounds down, having written GiB as GB
V5E_BYTES_LIMIT = 16_909_334_528


def test_mellum_decode_step_with_the_widest_chunk_compiles_for_v5e_in_place(
        on_v5e, monkeypatch):
    """`jit_paged_decode_step` of the Mellum 2 family at the cell's shapes
    (published widths, two periods of 8 layers, all 64 experts, 46 slots,
    12,288 blocks of 32, tables of 1,056) with the widest chunk: the decode
    rows' attention is the paged kernel in every layer, over the block pool
    in the two full layers and over the slots' rings, seen as a pool, in the
    six window layers; pool and rings are donated and not copied; a layer's
    experts are one call of the grouped kernel over the chunk's and the
    slots' 2,416 pairs (19 row tiles); caches of 1.61 + 0.72 GB; and
    weights, caches and temporaries fit under the chip's own limit."""
    from ray_tpu.llm import _mellum_steps
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import mellum

    monkeypatch.setattr(pa, "decode_path", lambda *a: (pa.KERNEL, None))
    monkeypatch.setattr(gf, "ffn_path", lambda *a: gf.KERNEL)
    cfg = mellum.MellumConfig(
        n_layers=8, layer_ids=tuple(range(8)), max_seq_len=33792)
    ecfg = EngineConfig(max_num_seqs=46, kv_block_size=32,
                        num_kv_blocks=12288, max_model_len=33792,
                        prefix_cache=False)
    assert cfg.kinds() == ["window"] * 3 + ["full"] + ["window"] * 3 + ["full"]
    C = _mellum_steps.chunk_ladder(ecfg)[-1]
    assert C == 256 and _mellum_steps._ring_positions(cfg, ecfg) == 1280
    step, path, note = _mellum_steps.make_decode_step(cfg, ecfg)
    assert (path, note) == (pa.KERNEL, None)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: mellum.init_params(cfg, jax.random.PRNGKey(0))))
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _mellum_steps.alloc_cache(cfg, ecfg))]
    B = 46
    compiled = step.trace(
        C, params, *caches, on_v5e((B, 1056), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((B,), jnp.bool_),
        on_v5e((B,), jnp.int32), on_v5e((B, 2), jnp.uint32),
        on_v5e((B,), jnp.float32),
        on_v5e((B + len(_mellum_steps.COUNTERS) + 3,), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((C,), jnp.int32),
        on_v5e((3,), jnp.int32), on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    kernels = [line for line in hlo.splitlines()
               if PALLAS in line and "%paged_decode_attention" in line]
    assert len(kernels) == 8
    # the full layers read the block pool, the window layers the rings as
    # 46 slots x 40 blocks a layer
    assert sum("bf16[24578,128,128]" in k for k in kernels) == 2
    assert sum("bf16[11040,128,128]" in k for k in kernels) == 6
    experts = grouped_ffn_calls(hlo)
    assert len(experts) == 8
    # 2,416 pairs, padded by the kernel's caller to 19 row tiles of 128
    assert all(res == ["bf16[2432,2304]"] and "bf16[64,2304,896]" in ops
               for ops, res in experts)
    # neither the pool nor the rings is copied whole
    assert not re.findall(r" copy\([^)]*(?:12289|6,46,1280)", hlo)
    m = compiled.memory_analysis()
    pool_bytes = sum(c.size * c.dtype.itemsize for c in caches[:2])
    ring_bytes = sum(c.size * c.dtype.itemsize for c in caches[2:])
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    assert 1.60e9 < pool_bytes < 1.62e9 and 0.72e9 < ring_bytes < 0.73e9
    assert 7.58e9 < weight_bytes < 7.60e9
    assert m.alias_size_in_bytes >= pool_bytes + ring_bytes
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_BYTES_LIMIT
    print("mellum step: weights %.3f GB pool %.3f GB rings %.3f GB temp "
          "%.3f GB" % (weight_bytes / 1e9, pool_bytes / 1e9, ring_bytes / 1e9,
                       m.temp_size_in_bytes / 1e9))


def test_joyai_decode_step_with_the_widest_chunk_compiles_for_v5e_in_place(
        on_v5e, monkeypatch):
    """`jit_paged_decode_step` of the JoyAI-LLM-Flash family at the cell's
    shapes (published widths, all 40 layers and the whole vocabulary, 16 of
    256 experts held, 16 slots, 6,144 blocks of 16, tables of 1,024) with
    the widest chunk, 256 rows: 4,776.5M parameters; the decode rows'
    attention is the paged kernel over the latent pool as one 640-wide head
    that is its own value (one operand, fetched once), once in the unrolled
    dense layer and once in the scanned expert layers'
    body; the held experts are the grouped kernel at 2,048 -> 768 over the
    39 layers' stack in place; the pool is donated and not copied; weights, pool and temporaries fit under the
    chip's own limit."""
    from ray_tpu.llm import _joyai_steps
    from ray_tpu.llm._engine import EngineConfig
    from ray_tpu.models import joyai

    monkeypatch.setattr(pa, "decode_path", lambda *a: (pa.KERNEL, None))
    monkeypatch.setattr(gf, "ffn_path", lambda *a: gf.KERNEL)
    cfg = joyai.JoyAIConfig(n_held=16)
    ecfg = EngineConfig(max_num_seqs=16, kv_block_size=16, num_kv_blocks=6144,
                        max_model_len=16384, prefix_cache=True)
    C = _joyai_steps.chunk_ladder(ecfg)[-1]
    assert C == 256
    step, path, note = _joyai_steps.make_decode_step(cfg, ecfg)
    assert (path, note) == (pa.KERNEL, None)

    def spec(x):
        return on_v5e(x.shape, x.dtype)

    params = jax.tree.map(spec, jax.eval_shape(
        lambda: joyai.init_params(cfg, jax.random.PRNGKey(0))))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert n_params == 4_776_521_472
    caches = [spec(c) for c in jax.eval_shape(
        lambda: _joyai_steps.alloc_cache(cfg, ecfg))]
    B = 16
    compiled = step.trace(
        C, params, *caches, on_v5e((B, 1024), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((B,), jnp.bool_),
        on_v5e((B,), jnp.int32), on_v5e((B, 2), jnp.uint32),
        on_v5e((B,), jnp.float32),
        on_v5e((B + len(_joyai_steps.COUNTERS) + 3,), jnp.int32),
        on_v5e((B,), jnp.int32), on_v5e((C,), jnp.int32),
        on_v5e((3,), jnp.int32), on_v5e((), jnp.int32),
    ).lower(lowering_platforms=("tpu",)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_paged_decode_step")
    # the dense layer's call and the scanned body's; each is handed the pool
    # once (PR 61: it was the keys and the values, and every live page moved
    # twice) and returns the value's 512 columns
    calls = latent_attention_calls(hlo)
    assert len(calls) == 2
    for ops, res in calls:
        assert ops.count("bf16[245800,16,640]") == 1
        assert res == ["bf16[16,32,512]"]
    experts = grouped_ffn_calls(hlo)
    assert len(experts) == 1
    # every layer's experts as one stack, read where they lie: no layer's
    # slice of it is copied out for the kernel
    assert all("bf16[624,2048,768]" in ops for ops, res in experts)
    assert "bf16[16,2048,768]" not in hlo
    # the pool is not copied whole
    assert not re.findall(r" copy\([^)]*40,6145", hlo)
    m = compiled.memory_analysis()
    pool_bytes = sum(c.size * c.dtype.itemsize for c in caches)
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(params))
    assert 5.03e9 < pool_bytes < 5.04e9
    assert 9.59e9 < weight_bytes < 9.60e9      # the routers are float32
    assert m.alias_size_in_bytes >= pool_bytes
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) < V5E_BYTES_LIMIT
    print("joyai step: weights %.3f GB pool %.3f GB temp %.3f GB" % (
        weight_bytes / 1e9, pool_bytes / 1e9, m.temp_size_in_bytes / 1e9))


@functools.lru_cache(maxsize=None)
def train_step_for_v5e(topo, remat):
    """`jit_train_step` at the train cell's shapes (InternLM2-1.8B whole,
    fsdp over the four described chips, one sequence of 4096 a chip, flash
    kernels) compiled for the described v5e, once a `remat` (~20 s each).
    Call it under `on_v5e`."""
    from jax.sharding import NamedSharding, PartitionSpec
    from jax.tree_util import keystr, tree_flatten_with_path

    from ray_tpu.models.llama import (LlamaConfig, make_train_step,
                                      param_specs)
    from ray_tpu.parallel.mesh import MeshSpec, logical_to_sharding

    cfg = LlamaConfig(
        vocab_size=92544, dim=2048, n_layers=24, n_heads=16, n_kv_heads=8,
        ffn_dim=8192, rope_theta=1e6, norm_eps=1e-5, max_seq_len=4096,
        attention_impl="flash")
    mesh = MeshSpec(fsdp=4).build(topo.devices)
    init_state, _, step, data_sharding = make_train_step(
        cfg, mesh, remat=remat)
    shapes = jax.eval_shape(init_state, jax.random.key(0))
    by_path = {keystr(path): s for (path, _), s in zip(
        tree_flatten_with_path(shapes[0])[0],
        jax.tree.leaves(logical_to_sharding(param_specs(cfg), mesh)))}

    def placed(path, leaf):
        # a moment lies as its parameter does (parallel.mesh.shard_train_state)
        ks = keystr(path)
        sharding = next((s for pk, s in by_path.items() if ks.endswith(pk)
                         and leaf.ndim == len(s.spec)),
                        NamedSharding(mesh, PartitionSpec()))
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sharding)

    state = jax.tree_util.tree_map_with_path(placed, shapes)
    tokens = jax.ShapeDtypeStruct((4, 4096), jnp.int32,
                                  sharding=data_sharding)
    return step.trace(state, tokens).lower(
        lowering_platforms=("tpu",)).compile()


def flash_calls(hlo: str):
    """[(kernel name, op_name)] of the flash kernels' custom calls in a
    compiled module's text: the instruction is named after the kernel."""
    return [(re.search(r"%(flash_[a-z]+)[.\w]* = ", line).group(1),
             re.search(r'op_name="([^"]*)"', line).group(1))
            for line in hlo.splitlines() if PALLAS in line]


def test_train_step_compiles_for_v5e_with_the_scopes_on_its_matmuls(
        on_v5e, topo):
    """The train cell's step under `remat="dots"`: every matmul fusion of
    the v5e program (what the device trace's `hlo_category` calls a
    `convolution fusion`) carries one top-level name of `TRAIN_SCOPES` in
    its `op_name`, a layer's `attn` or `mlp` under `layers`, and the
    recomputed ones `rematted_computation` where there are any: what
    benchmark/lib/xmeta.py reads on the chip."""
    hlo = train_step_for_v5e(topo, "dots").as_text()
    assert hlo.startswith("HloModule jit_train_step")
    with_matmul, name = set(), None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = m.group(1)
        elif " convolution(" in line:
            with_matmul.add(name)
    scopes = []
    for line in hlo.splitlines():
        called = re.search(r" fusion\(.*calls=%([\w.\-]+)", line)
        if called and called.group(1) in with_matmul:
            op_name = re.search(r'op_name="([^"]*)"', line)
            assert op_name, line[:200]
            toks = [t for t in re.split(r"[/()]", op_name.group(1)) if t]
            top = [t for t in toks if t in ("embed", "layers", "loss",
                                           "optimizer")]
            inner = [t for t in toks if t in ("attn", "mlp")]
            assert len(top) == 1 and (top == ["layers"]) == (len(inner) == 1), (
                op_name.group(1))
            scopes.append((top[0], *inner, "transpose" in toks))
    # a layer's seven matmuls forward, twice that backward; the head's
    # forward, recomputed and two backward ones
    assert len(scopes) >= 7 + 14 + 4, scopes
    assert {("layers", "attn", False), ("layers", "attn", True),
            ("layers", "mlp", False), ("layers", "mlp", True),
            ("loss", False), ("loss", True)} == set(scopes)
    # the flash kernels ride inside the scopes too
    kernels = [line for line in hlo.splitlines() if PALLAS in line]
    assert len(kernels) >= 3 and all(
        re.search(r'op_name="[^"]*layers[^"]*attn', k) for k in kernels)


def test_train_step_under_dots_runs_each_flash_kernel_once_a_layer(
        on_v5e, topo):
    """`remat="dots"` keeps the forward kernel's `o` and `lse`
    (`fa.RESIDUAL_NAMES`) through the `shard_map` around the kernel and the
    layer scan: the v5e program holds one `flash_fwd`, one `flash_dq` and
    one `flash_dkv` call (each once a scan), none of them recomputed, and
    the stacked residuals (24 x 16.8 MB of `o`, 24 x 0.26 MB of `lse` a
    chip) leave the step's temporaries under 11.0 GB (10.865; 10.451 with
    the second `flash_fwd` in their place)."""
    compiled = train_step_for_v5e(topo, "dots")
    calls = flash_calls(compiled.as_text())
    assert sorted(k for k, _ in calls) == ["flash_dkv", "flash_dq",
                                           "flash_fwd"], calls
    assert not [c for c in calls if "rematted_computation" in c[1]], calls
    where = dict(calls)
    assert "transpose(" not in where["flash_fwd"]
    assert "transpose(" in where["flash_dq"]
    assert "transpose(" in where["flash_dkv"]
    assert compiled.memory_analysis().temp_size_in_bytes < 11.0e9


def test_train_step_under_full_remat_still_recomputes_the_forward_kernel(
        on_v5e, topo):
    """`remat=True` has no policy: the whole layer is recomputed in the
    backward scan, its `flash_fwd` with it, whatever the names."""
    calls = flash_calls(train_step_for_v5e(topo, True).as_text())
    assert sorted(k for k, _ in calls) == ["flash_dkv", "flash_dq",
                                           "flash_fwd", "flash_fwd"], calls
    recomputed = [k for k, n in calls if "rematted_computation" in n]
    assert recomputed == ["flash_fwd"], calls

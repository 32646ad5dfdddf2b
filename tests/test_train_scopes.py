"""The train step's names (`models.llama.TRAIN_SCOPES`): every instruction of
the compiled step falls under one of embed / layers / loss / optimizer, attn
and mlp only inside layers, and the names change no value. The benchmark's
readers (benchmark/lib/xmeta.py) find the scopes in the device trace's
`tf_op`, which is this `op_name`."""

import collections
import re

import jax
import pytest

from ray_tpu.models.llama import TRAIN_SCOPES, LlamaConfig, make_train_step
from ray_tpu.parallel.mesh import MeshSpec

TOP = ("embed", "layers", "loss", "optimizer")
INNER = ("attn", "mlp")
B, S, CHUNK = 4, 64, 16
# the step's loss on these weights and tokens before the scopes existed
# (the parent commit, every `remat`, float for float)
LOSS_BEFORE = 7.09980583190918


def compiled(remat, fsdp=4):
    cfg = LlamaConfig.tiny(vocab_size=768)
    mesh = MeshSpec(fsdp=fsdp).build(jax.devices()[:fsdp])
    init_state, shard_state, step, data_sharding = make_train_step(
        cfg, mesh, remat=remat, loss_chunk=CHUNK)
    state = shard_state(init_state(jax.random.PRNGKey(0)))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size),
        data_sharding)
    return step, state, tokens, step.lower(state, tokens).compile().as_text()


def op_names(hlo):
    """The `op_name` of every instruction that has a whole one. (A
    reduction's scalar body carries the bare primitive, `reduce_sum`: it
    never runs as an operation of its own.)"""
    return re.findall(r'op_name="(jit\(train_step\)/[^"]*)"', hlo)


def tokens_of(op_name):
    return [t for t in re.split(r"[/()]", op_name) if t]


def test_the_constant_names_the_scopes():
    assert TRAIN_SCOPES == ("embed", "layers", "attn", "mlp", "loss",
                            "optimizer")
    assert set(TOP) | set(INNER) == set(TRAIN_SCOPES)


@pytest.mark.parametrize("remat", [False, "ffn", "dots", True],
                         ids=["none", "ffn", "dots", "full"])
def test_every_instruction_falls_under_one_top_level_scope(remat):
    step, state, tokens, hlo = compiled(remat)
    names = op_names(hlo)
    assert len(names) > 1000
    tops = collections.Counter(
        tuple(sorted({t for t in tokens_of(n) if t in TOP})) for n in names)
    one = sum(v for k, v in tops.items() if len(k) == 1)
    # what is left: masks of the XLA attention hoisted out of the scan by
    # partial evaluation, which keep `attn` and lose the scan's `layers`
    assert one >= 0.99 * len(names), tops
    assert not [k for k in tops if len(k) > 1], tops
    assert {k[0] for k in tops if k} == set(TOP)
    for n in names:
        toks = tokens_of(n)
        if set(toks) & set(INNER) and "jit(tril)" not in n and "_where" not in n:
            assert "layers" in toks, n
    # the loss's backward matmul, traced on its own as a custom_vjp's
    # backward function, inherits the scope of the call
    assert [n for n in names if "loss" in tokens_of(n) and "transpose(" in n
            and n.endswith("dot_general")]
    # AdamW's update and the applied updates
    assert [n for n in names if "optimizer" in tokens_of(n)
            and n.endswith(("sqrt", "add"))]
    # the attention's and the FFN's matmuls, forward and backward
    for inner in INNER:
        for transform in ("jvp(layers)", "transpose(jvp(layers))"):
            assert [n for n in names if inner in tokens_of(n)
                    and transform in n and n.endswith("dot_general")], (
                inner, transform)
    remats = [n for n in names if "rematted_computation" in tokens_of(n)]
    assert bool(remats) == bool(remat)
    assert all("layers" in tokens_of(n) for n in remats)
    # the names change no value
    _, loss = step(state, tokens)
    assert float(loss) == pytest.approx(LOSS_BEFORE, rel=1e-6)

"""The flash kernel's `o` and `lse` are named residuals
(`ops.flash_attention.RESIDUAL_NAMES`) and `make_train_step`'s
`remat="dots"` keeps them: the backward pass of a checkpointed layer reads
the values the forward kernel wrote and does not run it a second time. The
kernels run in the Pallas interpreter here (`fa._INTERPRET`); the compiled
program for the chip is held in tests/test_v5e_compile.py."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig, make_train_step
from ray_tpu.ops import flash_attention as fa
from ray_tpu.parallel.mesh import MeshSpec

POLICIES = jax.checkpoint_policies
DOTS = POLICIES.dots_with_no_batch_dims_saveable
B, S = 2, 256


@pytest.fixture()
def interpreted(monkeypatch):
    monkeypatch.setattr(fa, "_INTERPRET", True)


def tiny_flash():
    return LlamaConfig.tiny(dim=256, n_heads=2, n_kv_heads=1, ffn_dim=512,
                            vocab_size=384, attention_impl="flash")


def all_eqns(jaxpr):
    """The equations of a jaxpr, sub-jaxprs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from all_eqns(sub)


def kernel_calls(jaxpr):
    return [e.params["name"] for e in all_eqns(jaxpr)
            if e.primitive.name == "pallas_call"]


def primitives(jaxpr):
    return [e.primitive.name for e in all_eqns(jaxpr)]


def layer_and_inputs():
    cfg = tiny_flash()
    params = llama.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.key(1), (B, S, cfg.dim), cfg.dtype)
    cos, sin = llama.rope_tables(cfg, jnp.arange(S, dtype=jnp.int32))
    return partial(llama._layer, cfg, None), h, lp, cos, sin


def layer_grad(policy):
    layer, h, lp, cos, sin = layer_and_inputs()
    layer = jax.checkpoint(layer, policy=policy)

    def loss(h, lp):
        return jnp.sum(layer(h, lp, cos, sin).astype(jnp.float32) ** 2)

    grad = jax.grad(loss, argnums=(0, 1))
    return grad, (h, lp)


def test_the_names_are_the_modules_constants():
    assert fa.RESIDUAL_NAMES == (fa.FLASH_OUT, fa.FLASH_LSE)
    assert len(set(fa.RESIDUAL_NAMES)) == 2


def test_a_layer_under_the_new_policy_runs_the_forward_kernel_once(
        interpreted):
    """Old policy (the dots alone): `flash_fwd` twice in the gradient, once
    forward and once recomputed. New policy (the dots and the two names):
    once. The gradients are the same bits: the saved values are the ones
    the second run produced."""
    old, args = layer_grad(DOTS)
    new, _ = layer_grad(POLICIES.save_from_both_policies(
        DOTS, POLICIES.save_only_these_names(*fa.RESIDUAL_NAMES)))
    assert sorted(kernel_calls(jax.make_jaxpr(old)(*args).jaxpr)) == [
        "flash_dkv", "flash_dq", "flash_fwd", "flash_fwd"]
    assert sorted(kernel_calls(jax.make_jaxpr(new)(*args).jaxpr)) == [
        "flash_dkv", "flash_dq", "flash_fwd"]
    got, expected = jax.jit(new)(*args), jax.jit(old)(*args)
    leaves = jax.tree.leaves(got)
    assert len(leaves) == 1 + 9 and all(
        np.any(np.asarray(x, np.float32)) for x in leaves)
    jax.tree.map(lambda g, e: np.testing.assert_array_equal(
        np.asarray(g, np.float32), np.asarray(e, np.float32)), got, expected)


@pytest.mark.parametrize("names,fwd_calls", [
    ((), 2), ((fa.FLASH_OUT,), 2), ((fa.FLASH_LSE,), 2),
    (fa.RESIDUAL_NAMES, 1)], ids=["neither", "o", "lse", "both"])
def test_the_backward_reads_both_names(interpreted, names, fwd_calls):
    """One name alone saves nothing: the kernel writes both in one call."""
    grad, args = layer_grad(POLICIES.save_from_both_policies(
        DOTS, POLICIES.save_only_these_names(*names)))
    calls = kernel_calls(jax.make_jaxpr(grad)(*args).jaxpr)
    assert calls.count("flash_fwd") == fwd_calls


def step_and_inputs(remat, fsdp):
    cfg = tiny_flash()
    mesh = MeshSpec(fsdp=fsdp).build(jax.devices()[:fsdp])
    init_state, shard_state, step, data_sharding = make_train_step(
        cfg, mesh, remat=remat, loss_chunk=64)
    state = shard_state(init_state(jax.random.key(0)))
    tokens = jax.device_put(
        jax.random.randint(jax.random.key(1), (B, S), 0, cfg.vocab_size),
        data_sharding)
    return step, state, tokens


@pytest.mark.parametrize("fsdp", [1, 2], ids=["one_chip", "shard_map"])
@pytest.mark.parametrize("remat,fwd_calls", [
    (False, 1), ("ffn", 1), ("dots", 1), (True, 2)],
    ids=["none", "ffn", "dots", "full"])
def test_train_step_forward_kernel_calls(interpreted, remat, fwd_calls, fsdp):
    """`remat="dots"` runs the forward kernel once a layer, alone or through
    the `shard_map` that wraps it on a mesh; `remat=True` still recomputes
    the whole layer, the kernel with it."""
    step, state, tokens = step_and_inputs(remat, fsdp)
    calls = kernel_calls(jax.make_jaxpr(step)(state, tokens).jaxpr)
    assert sorted(calls) == sorted(
        ["flash_dkv", "flash_dq"] + ["flash_fwd"] * fwd_calls)


def test_train_step_under_dots_is_bitwise_the_old_policys(interpreted,
                                                          monkeypatch):
    """The step `make_train_step` builds for "dots", against the same step
    with the names saved by no policy (what "dots" was): the loss and every
    updated parameter and moment are the same bits."""
    step, state, tokens = step_and_inputs("dots", 1)
    (new_params, new_opt), new_loss = step(state, tokens)
    monkeypatch.setattr(POLICIES, "save_only_these_names",
                        lambda *names: POLICIES.nothing_saveable)
    step, state, tokens = step_and_inputs("dots", 1)
    assert kernel_calls(jax.make_jaxpr(step)(state, tokens).jaxpr).count(
        "flash_fwd") == 2
    (old_params, old_opt), old_loss = step(state, tokens)
    assert float(new_loss) == float(old_loss) and np.isfinite(float(new_loss))
    jax.tree.map(lambda g, e: np.testing.assert_array_equal(
        np.asarray(g), np.asarray(e)), (new_params, new_opt),
        (old_params, old_opt))


def test_outside_a_checkpoint_the_names_are_the_identity(interpreted):
    """No `jax.checkpoint` around it: the gradient holds the three kernels
    once each and the two names, and value and gradients are the bits the
    kernels give when called with no name between them."""
    h, kvh, hd = 2, 1, 128
    q = jax.random.normal(jax.random.key(0), (B, h, S, hd), jnp.bfloat16)
    k = jax.random.normal(jax.random.key(1), (B, kvh, S, hd), jnp.bfloat16)
    v = jax.random.normal(jax.random.key(2), (B, kvh, S, hd), jnp.bfloat16)
    g = jax.random.normal(jax.random.key(3), (B, h, S, hd), jnp.bfloat16)

    def named(q, k, v):
        o, vjp = jax.vjp(fa.flash_attention_bhsd, q, k, v)
        return (o, *vjp(g))

    def plain(q, k, v):
        o, lse = fa._flash_fwd_tpu(q, k, v, True, S, S)
        return (o, *fa._flash_bwd_tpu(q, k, v, o, lse, g, True, S, S,
                                      dkv_block_q=S, dkv_block_k=S))

    jaxpr = jax.make_jaxpr(named)(q, k, v)
    assert kernel_calls(jaxpr.jaxpr) == ["flash_fwd", "flash_dq", "flash_dkv"]
    assert primitives(jaxpr.jaxpr).count("name") == 2
    without = [p for p in primitives(jaxpr.jaxpr) if p != "name"]
    assert without == primitives(jax.make_jaxpr(plain)(q, k, v).jaxpr)
    assert jaxpr.out_avals == jax.make_jaxpr(plain)(q, k, v).out_avals
    for got, expected in zip(jax.jit(named)(q, k, v), jax.jit(plain)(q, k, v)):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(expected, np.float32))
    # the forward alone (no gradient asked for) never meets the names
    assert "name" not in primitives(
        jax.make_jaxpr(fa.flash_attention_bhsd)(q, k, v).jaxpr)

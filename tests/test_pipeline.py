"""Pipeline parallelism (GPipe over the "pp" mesh axis) — loss parity with
the single-stage trainer and composition with dp/tp (reference capability:
python/ray/dag/compiled_dag_node.py:813 — PP via compiled actor DAGs; here
it is an in-jit SPMD schedule, ray_tpu/parallel/pipeline.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, make_train_step
from ray_tpu.parallel.mesh import MeshSpec
from ray_tpu.parallel.pipeline import (
    make_pipeline_train_step, stack_stages, unstack_stages,
)

CFG = LlamaConfig(
    vocab_size=128, dim=64, n_layers=4, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=32,
    dtype=jnp.float32, param_dtype=jnp.float32,
)

def _tokens(batch=8, seq=32):
    return jax.random.randint(
        jax.random.key(1), (batch, seq), 0, CFG.vocab_size, dtype=jnp.int32)


def _run_single_stage(tokens, steps=2, lr=1e-2):
    mesh = MeshSpec().build(jax.devices()[:1])
    init, shard, step, ds = make_train_step(CFG, mesh, learning_rate=lr)
    state = shard(init(jax.random.key(0)))
    losses = []
    for _ in range(steps):
        state, loss = step(state, jax.device_put(tokens, ds))
        losses.append(float(loss))
    return losses


def _run_pipelined(tokens, spec: MeshSpec, n_micro, steps=2, lr=1e-2):
    mesh = spec.build(jax.devices()[: spec.num_devices])
    init, shard, step, ds = make_pipeline_train_step(
        CFG, mesh, n_microbatches=n_micro, learning_rate=lr)
    state = shard(init(jax.random.key(0)))
    losses = []
    for _ in range(steps):
        state, loss = step(state, jax.device_put(tokens, ds))
        losses.append(float(loss))
    return losses


def test_two_stage_loss_parity_with_single_stage():
    """The VERDICT's done-criterion: a 2-stage split trains with loss parity
    against single-stage (same init, same data, same optimizer)."""
    tokens = _tokens()
    base = _run_single_stage(tokens)
    pp = _run_pipelined(tokens, MeshSpec(pp=2), n_micro=4)
    np.testing.assert_allclose(base, pp, rtol=2e-3)


def test_pipeline_composes_with_dp_and_tp():
    tokens = _tokens()
    base = _run_single_stage(tokens)
    pp = _run_pipelined(tokens, MeshSpec(pp=2, dp=2, tp=2), n_micro=2)
    np.testing.assert_allclose(base, pp, rtol=2e-3)


def test_four_stage_deep_pipeline():
    tokens = _tokens()
    base = _run_single_stage(tokens)
    pp = _run_pipelined(tokens, MeshSpec(pp=4), n_micro=8)
    np.testing.assert_allclose(base, pp, rtol=2e-3)


def test_stage_stacking_roundtrip():
    params = {"w": jnp.arange(24.0).reshape(4, 3, 2)}
    stacked = stack_stages(params, 2)
    assert stacked["w"].shape == (2, 2, 3, 2)
    np.testing.assert_array_equal(unstack_stages(stacked)["w"], params["w"])


def test_uneven_stage_split_rejected():
    mesh = MeshSpec(pp=2).build(jax.devices()[:2])
    bad = LlamaConfig(
        vocab_size=64, dim=32, n_layers=3, n_heads=2, n_kv_heads=2,
        ffn_dim=64, max_seq_len=16, dtype=jnp.float32,
        param_dtype=jnp.float32)
    with pytest.raises(AssertionError, match="divide"):
        make_pipeline_train_step(bad, mesh, n_microbatches=2)

"""The chunked loss of `models.llama.make_train_step`: the output head crosses
the chips once a step in each direction, whatever the number of chunks, and
the chunked step is the unchunked step's mathematics."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, make_train_step
from ray_tpu.parallel.mesh import MeshSpec

ADAM_B1 = 0.9  # optax.adamw's default, which make_train_step uses
MESHES = {"fsdp4": MeshSpec(fsdp=4), "fsdp2_tp2": MeshSpec(fsdp=2, tp=2)}
B, S, CHUNK = 4, 64, 16


def tiny(**kw):
    # a vocabulary no other matrix of the model shares a dimension with
    return LlamaConfig.tiny(vocab_size=768, **kw)


def built(cfg, spec, loss_chunk):
    mesh = spec.build(jax.devices()[:spec.num_devices])
    init_state, shard_state, step, data_sharding = make_train_step(
        cfg, mesh, remat="dots", loss_chunk=loss_chunk)
    state = shard_state(init_state(jax.random.PRNGKey(0)))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size),
        data_sharding)
    return step, state, tokens


def loss_and_grads(cfg, spec, loss_chunk):
    """One step from zero moments: Adam's first moment is (1 - b1) x the
    gradient, exactly."""
    step, state, tokens = built(cfg, spec, loss_chunk)
    (_, opt_state), loss = step(state, tokens)
    return float(loss), jax.tree.map(
        lambda m: np.asarray(m) / (1 - ADAM_B1), opt_state[0].mu)


@pytest.mark.parametrize("mesh", MESHES)
def test_chunked_step_is_the_unchunked_step(mesh):
    cfg = tiny(dtype=jnp.float32)
    loss_c, grads_c = loss_and_grads(cfg, MESHES[mesh], CHUNK)
    loss_u, grads_u = loss_and_grads(cfg, MESHES[mesh], 0)
    assert abs(loss_c - loss_u) <= 1e-6 * abs(loss_u)
    for (path, c), u in zip(jax.tree_util.tree_flatten_with_path(grads_c)[0],
                            jax.tree.leaves(grads_u)):
        assert np.abs(u).max() > 0, path
        np.testing.assert_allclose(
            c, u, rtol=0, atol=2e-6 * np.abs(u).max(),
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("mesh", MESHES)
def test_chunked_bf16_step_holds_to_the_float32_one(mesh):
    """In the compute dtype the cells run: the head's gradient is rounded
    once a shard a step, so it stays within a few bf16 steps of float32's."""
    _, want = loss_and_grads(tiny(dtype=jnp.float32), MESHES[mesh], CHUNK)
    _, got = loss_and_grads(tiny(), MESHES[mesh], CHUNK)
    scale = np.abs(want["lm_head"]).max()
    assert np.abs(got["lm_head"] - want["lm_head"]).max() <= 0.05 * scale


def computations(hlo):
    """{computation name: its lines} of a compiled module's text, and the
    entry's name."""
    out, entry, name = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m and not line.startswith(" "):
            name = m.group(2)
            out[name] = []
            if m.group(1):
                entry = name
        elif name is not None:
            out[name].append(line)
    return out, entry


def head_collectives(hlo, dim, vocab_per_shard):
    """(computation, kind) of every collective that moves a whole head:
    a result (or a member of a combined result) of (dim, vocab / tp)."""
    shape = re.compile(rf"\[(?:1,)?{dim},{vocab_per_shard}\]")
    kinds = re.compile(
        r" = (.*?) (all-gather|all-reduce|reduce-scatter)(?:-start)?\(")
    comps, entry = computations(hlo)
    found = []
    for name, lines in comps.items():
        for line in lines:
            m = kinds.search(line)
            if m and shape.search(m.group(1)):
                found.append(("entry" if name == entry else name, m.group(2)))
    return found


@pytest.mark.parametrize("mesh", MESHES)
def test_head_crosses_the_chips_once_a_step_each_way(mesh):
    spec = MESHES[mesh]
    cfg = tiny()
    step, state, tokens = built(cfg, spec, CHUNK)
    hlo = step.lower(state, tokens).compile().as_text()
    assert S // CHUNK == 4 and hlo.count(" while(") >= 4  # the loop is there
    found = head_collectives(hlo, cfg.dim, cfg.vocab_size // spec.tp)
    # one gather, one reduction, both outside every loop
    assert sorted(found) == [("entry", "all-gather"), ("entry", "all-reduce")]


def test_chunk_that_does_not_divide_the_sequence_takes_the_unchunked_branch():
    cfg = tiny()
    texts = []
    for loss_chunk in (0, 48, S):  # 64 % 48 != 0; a chunk of the whole row
        step, state, tokens = built(cfg, MESHES["fsdp4"], loss_chunk)
        texts.append(step.lower(state, tokens).as_text())
    assert texts[0] == texts[1] == texts[2]
    step, state, tokens = built(cfg, MESHES["fsdp4"], CHUNK)
    assert step.lower(state, tokens).as_text() != texts[0]

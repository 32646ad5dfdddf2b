"""Training batches drawn on the device from (`--seed`, step).

Token ids follow a Zipf-like law, P(id = k) ~ 1 / (k + 1) (an id is
floor(V^u) - 1 for uniform u), so that there is something to learn: the loss
starts near ln V and falls as the model picks up the unigram frequencies.

Parameters (the traffic file):
  seq_len            tokens a sequence
  sequences_per_chip sequences each chip holds in a step
  remat, report_every, warmup_steps   the job's settings, read by the runner
"""

from __future__ import annotations

from typing import Callable, Dict

LOOP = "train"


def batch_fn(params: Dict, vocab_size: int, chips: int) -> Callable:
    """`fn(seed, step) -> int32 [chips * sequences_per_chip, seq_len]`, to be
    jitted by the caller with the data sharding as `out_shardings`."""
    import jax
    import jax.numpy as jnp

    shape = (int(params["sequences_per_chip"]) * chips, int(params["seq_len"]))

    def fn(seed, step):
        key = jax.random.fold_in(jax.random.key(seed), step)
        u = jax.random.uniform(key, shape, jnp.float32)
        ids = jnp.floor(jnp.exp(u * jnp.log(float(vocab_size)))) - 1.0
        return jnp.clip(ids, 0, vocab_size - 1).astype(jnp.int32)

    return fn


def tokens_per_step(params: Dict, chips: int) -> int:
    return int(params["sequences_per_chip"]) * chips * int(params["seq_len"])

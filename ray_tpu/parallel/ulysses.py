"""Ulysses-style sequence parallelism: all-to-all head-scatter / seq-gather.

The second SP strategy (beside ring attention): instead of rotating K/V, an
all-to-all over the "sp" axis re-shards activations from sequence-sharded to
head-sharded, runs ordinary (full-sequence) attention locally on 1/sp of the
heads, and all-to-alls back. Communication volume is 2 all-to-alls instead of
(sp-1) ppermutes; on TPU the all-to-all maps onto the ICI torus natively.

Reference gap being filled: SURVEY §2b/§5 "Long-context / sequence
parallelism — not present in the reference".
"""

from __future__ import annotations

import jax
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.mesh import BATCH_AXES


def ulysses_attention_sharded(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh, causal: bool = True
) -> jax.Array:
    """q/k/v: (batch, seq, heads, head_dim), seq sharded over "sp".

    Requires heads % sp == 0 (and kv_heads % sp == 0 for GQA).
    """
    spec = P(BATCH_AXES, "sp", None, None)
    sp = mesh.shape["sp"]
    if q.shape[2] % sp or k.shape[2] % sp:
        raise ValueError(
            f"ulysses needs heads divisible by sp={sp}; "
            f"got q heads {q.shape[2]}, kv heads {k.shape[2]}"
        )

    def local_fn(q, k, v):
        # (b, s/sp, h, hd) -> (b, s, h/sp, hd): scatter heads, gather seq
        def scatter(x):
            return lax.all_to_all(x, "sp", split_axis=2, concat_axis=1,
                                  tiled=True)

        def gather(x):
            return lax.all_to_all(x, "sp", split_axis=1, concat_axis=2,
                                  tiled=True)

        from ray_tpu.ops.flash_attention import flash_attention

        ql, kl, vl = scatter(q), scatter(k), scatter(v)
        # local full-sequence attention on 1/sp of the heads rides the
        # Pallas flash kernel on TPU (fwd+bwd, no (s, s) materialization);
        # unsupported shapes/backends fall back to fused XLA inside
        out = flash_attention(ql, kl, vl, causal=causal)
        return gather(out)

    return shard_map(
        local_fn, mesh=mesh,
        in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)

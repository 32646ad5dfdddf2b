"""Expert parallelism: mixture-of-experts FFN with token-choice routing.

Capability the reference delegates to vLLM/DeepSpeed (SURVEY §2b EP row:
"Delegated to vLLM via engine_kwargs... shard_map expert axis + ragged
all-to-all over ICI" is the TPU-native equivalent to build). This is that
equivalent: GShard-style top-k routing with capacity buckets, experts
sharded over a mesh axis, tokens exchanged with `jax.lax.all_to_all` over
ICI, compute done as batched einsums on the MXU.

Design notes (TPU-first):
- dispatch/combine are dense one-hot einsums (static shapes — XLA tiles
  them onto the MXU; no dynamic gather in the hot path).
- capacity dropping keeps shapes static: tokens over an expert's capacity
  fall through the residual (standard GShard semantics).
- the EP path runs inside shard_map: dispatch buckets [E, C, d] are
  exchanged with all_to_all(split experts / concat capacity), each shard
  runs its local experts over every shard's tokens, and the reverse
  all_to_all brings expert outputs home.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int,
                    dtype=jnp.float32) -> Dict[str, jnp.ndarray]:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    return {
        "router": (jax.random.normal(k1, (d_model, num_experts)) * scale_in
                   ).astype(dtype),
        "w_in": (jax.random.normal(k2, (num_experts, d_model, d_ff))
                 * scale_in).astype(dtype),
        "w_out": (jax.random.normal(k3, (num_experts, d_ff, d_model))
                  * scale_out).astype(dtype),
    }


def _route(router_logits: jnp.ndarray, top_k: int, capacity: int
           ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Token-choice top-k routing → (dispatch [T,E,C], combine [T,E,C],
    aux_loss). One-hot capacity bucketing à la GShard/Switch."""
    T, E = router_logits.shape
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # [T,k]
    # renormalize the kept gates
    gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    # position of each (token, k) within its expert's capacity bucket:
    # flatten assignments in (k, token) priority order so k=0 choices win
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # [T,k,E]
    flat = onehot.transpose(1, 0, 2).reshape(top_k * T, E)   # [(k,T),E]
    pos_flat = jnp.cumsum(flat, axis=0) - flat               # rank per expert
    pos = pos_flat.reshape(top_k, T, E).transpose(1, 0, 2)   # [T,k,E]
    position = (pos * onehot).sum(-1)                        # [T,k]
    kept = position < capacity

    disp = (
        jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(position, capacity, dtype=jnp.float32)[:, :, None, :]
        * kept[..., None, None]
    )  # [T,k,E,C]
    dispatch = disp.sum(1)                                   # [T,E,C]
    combine = (disp * gate_vals[..., None, None]).sum(1)     # [T,E,C]

    # load-balancing auxiliary loss (Switch Transformer eq. 4)
    me = probs.mean(0)                                       # mean router prob
    ce = jax.nn.one_hot(expert_idx[:, 0], E).mean(0)         # top-1 load
    aux = E * (me * ce).sum()
    return dispatch, combine, aux


def _expert_ffn(w_in: jnp.ndarray, w_out: jnp.ndarray,
                x: jnp.ndarray) -> jnp.ndarray:
    """Batched expert FFN: x [E, C, d] → [E, C, d] (MXU batched matmuls)."""
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, w_in))
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def moe_ffn(params: Dict[str, jnp.ndarray], x: jnp.ndarray, *,
            top_k: int = 2, capacity_factor: float = 2.0
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-shard MoE FFN. x: [tokens, d_model] → (y, aux_loss)."""
    T, _d = x.shape
    E = params["router"].shape[1]
    capacity = max(1, int(np.ceil(T / E * capacity_factor * top_k)))
    dispatch, combine, aux = _route(x @ params["router"], top_k, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
    expert_out = _expert_ffn(params["w_in"], params["w_out"], expert_in)
    y = jnp.einsum("tec,ecd->td", combine, expert_out)
    return y.astype(x.dtype), aux


def moe_ffn_ep(params: Dict[str, jnp.ndarray], x: jnp.ndarray, *,
               mesh: Mesh, axis: str = "tp", tokens_spec: Optional[P] = None,
               top_k: int = 2, capacity_factor: float = 2.0
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE FFN over mesh axis `axis`.

    Experts are sharded over `axis` (params['w_in']/['w_out'] leading dim);
    tokens are sharded over `tokens_spec` (default: data axes). Within
    shard_map, each shard routes its local tokens to ALL experts, buckets
    them, and a pair of all_to_alls moves buckets to the expert owners and
    the outputs back — the ragged exchange rides ICI as one collective.
    """
    ep = mesh.shape[axis]
    E = params["router"].shape[1]
    assert E % ep == 0, f"num_experts {E} must divide ep={ep}"
    tokens_spec = tokens_spec if tokens_spec is not None else P("dp")
    token_axes: tuple = ()
    for part in tokens_spec:
        if part is None:
            continue
        token_axes += tuple(part) if isinstance(part, (tuple, list)) else (part,)

    def local(px, x_local):
        T_local = x_local.shape[0]
        capacity = max(1, int(np.ceil(T_local / E * capacity_factor * top_k)))
        dispatch, combine, aux = _route(
            x_local @ px["router"], top_k, capacity)
        buckets = jnp.einsum("tec,td->ecd", dispatch, x_local)  # [E,C,d]
        # exchange: split experts across shards, stack the senders' buckets
        # along capacity → [E/ep, C*ep, d] of tokens bound for MY experts
        incoming = jax.lax.all_to_all(
            buckets, axis, split_axis=0, concat_axis=1, tiled=True)
        outgoing = _expert_ffn(px["w_in"], px["w_out"], incoming)
        # reverse exchange: send each shard back its tokens' outputs
        returned = jax.lax.all_to_all(
            outgoing, axis, split_axis=1, concat_axis=0, tiled=True)
        y = jnp.einsum("tec,ecd->td", combine, returned)
        # average of per-shard aux losses over the token-sharding axes: a
        # standard distributed estimate of the global balance loss. aux is
        # invarying over the ep axis (x is replicated there), so reducing
        # over it would be rejected by shard_map's varying-axis typing.
        if token_axes:
            aux = jax.lax.pmean(aux, token_axes)
        return y.astype(x_local.dtype), aux

    param_specs = {
        "router": P(),            # replicated
        "w_in": P(axis),          # experts sharded over the ep axis
        "w_out": P(axis),
    }
    return shard_map(
        local, mesh=mesh,
        in_specs=(param_specs, tokens_spec),
        out_specs=(tokens_spec, P()),
        # y/aux are replicated over the ep axis by construction (the reverse
        # all_to_all returns every token's outputs to its home shard), which
        # the varying-axis checker cannot infer through the exchange
        check_vma=False,
    )(params, x)

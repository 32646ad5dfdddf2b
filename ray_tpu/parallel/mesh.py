"""Device meshes and sharding rules — the TPU-native parallelism substrate.

This replaces the reference's orchestration-only parallelism (Ray places
NCCL/DeepSpeed workers but delegates TP/PP/SP to them — SURVEY §2b) with
in-framework GSPMD: a named `jax.sharding.Mesh` over ICI with axes

    pp    — pipeline parallel (layer stages, ppermute activation hand-off)
    dp    — data parallel (gradient allreduce)
    fsdp  — fully-sharded data parallel (ZeRO-3-style param sharding)
    tp    — tensor parallel (megatron-style column/row sharding)
    sp    — sequence/context parallel (ring attention / Ulysses)

`pp` is the OUTERMOST axis: stage hand-offs move one activation tensor per
tick (the lowest-bandwidth traffic), so they get the slowest links — across
slices/DCN on real pods — while tp/sp stay innermost on ICI (scaling-book
axis-ordering recipe).

Reference for the capability being replaced: python/ray/train/v2/jax/config.py
(jax.distributed bootstrap), python/ray/llm/_internal/common/placement.py:47
(TP via placement groups + vLLM-internal NCCL).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXES = ("pp", "dp", "fsdp", "tp", "sp")


@dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. Axis size 1 = that parallelism disabled."""

    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    @property
    def shape(self) -> Tuple[int, int, int, int, int]:
        return (self.pp, self.dp, self.fsdp, self.tp, self.sp)

    @property
    def num_devices(self) -> int:
        return self.pp * self.dp * self.fsdp * self.tp * self.sp

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        """Build a named Mesh.

        Device order matters on real hardware: jax.devices() for TPU is
        ICI-topology-ordered, so adjacent mesh coordinates are ICI neighbors
        and `ppermute` rings ride ICI links. (Scaling-book recipe: innermost
        mesh axes get the fastest interconnect — keep tp/sp innermost.)
        """
        if devices is None:
            devices = jax.devices()
        if len(devices) < self.num_devices:
            raise ValueError(
                f"mesh {self.shape} needs {self.num_devices} devices, "
                f"have {len(devices)}"
            )
        arr = np.asarray(devices[: self.num_devices]).reshape(self.shape)
        return Mesh(arr, AXES)

    @classmethod
    def for_devices(cls, n: int, tp: int = 1, sp: int = 1) -> "MeshSpec":
        """A sensible default: fill remaining devices with fsdp."""
        rest = n // (tp * sp)
        return cls(dp=1, fsdp=rest, tp=tp, sp=sp)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

# Batch is sharded over both data axes; sequence over sp.
BATCH_AXES = ("dp", "fsdp")


def data_spec() -> P:
    """(batch, seq) token arrays."""
    return P(BATCH_AXES, "sp")


def activation_spec() -> P:
    """(batch, seq, model) activations."""
    return P(BATCH_AXES, "sp", None)


@dataclass
class ShardingRules:
    """Logical-name → PartitionSpec table, resolved against a mesh.

    The pattern follows GSPMD practice: parameters carry megatron-style tp
    sharding on their 'parallel' dimension and fsdp sharding on the other;
    XLA inserts all-gathers/reduce-scatters (ZeRO-3 semantics) automatically.
    """

    rules: Dict[str, P] = field(default_factory=dict)

    def spec(self, name: str) -> P:
        return self.rules.get(name, P())

    def sharding(self, mesh: Mesh, name: str) -> NamedSharding:
        return NamedSharding(mesh, self.spec(name))


def logical_to_sharding(tree_specs, mesh: Mesh):
    """Map a pytree of PartitionSpecs to NamedShardings on `mesh`."""
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def constrain(x, mesh: Mesh, spec: P):
    """In-jit sharding constraint (the GSPMD annotation primitive)."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def to_varying(x, axes):
    """Mark `x` as varying over manual mesh `axes` inside shard_map
    (shared by ring_attention/pipeline)."""
    return jax.lax.pcast(x, tuple(axes), to="varying")


def host_local_mesh_info(mesh: Mesh) -> dict:
    """Describe which mesh coordinates are on this host (multi-host SPMD)."""
    local = set(jax.local_devices())
    coords = [
        tuple(int(i) for i in idx)
        for idx, d in np.ndenumerate(mesh.devices)
        if d in local
    ]
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_coords": coords,
    }


def shard_train_state(params, opt_state, param_shardings, mesh: Mesh):
    """Place (params, opt_state) on `mesh`: params by their shardings,
    optimizer moments by key-path suffix match against the param tree.

    Moments mirror the param tree inside optax's state, so each moment
    leaf's key path ENDS with its param's key path — match on that suffix
    (shape alone is ambiguous: wq/wk/wv/wo coincide whenever
    n_heads*head_dim == dim, and a transposed spec would silently force a
    per-step reshard of donated optimizer state). Scalars and unmatched
    leaves are replicated. Shared by every model's make_train_step
    (models/llama.py, models/vit.py).
    """
    from jax.tree_util import keystr, tree_flatten_with_path

    replicated = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda x, s: jax.device_put(x, s), params, param_shardings
    )
    param_paths = [
        (keystr(path), leaf.shape, sharding)
        for (path, leaf), sharding in zip(
            tree_flatten_with_path(params)[0],
            jax.tree.leaves(
                param_shardings,
                is_leaf=lambda s: isinstance(s, NamedSharding),
            ),
        )
    ]

    def sharding_for(opt_path, x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return replicated
        ks = keystr(opt_path)
        for pk, shape, sharding in param_paths:
            if ks.endswith(pk) and x.shape == shape:
                return sharding
        return replicated

    flat, treedef = tree_flatten_with_path(opt_state)
    placed = [jax.device_put(x, sharding_for(path, x)) for path, x in flat]
    return params, jax.tree.unflatten(treedef, placed)

"""Public Train API: configs + DataParallelTrainer + JaxTrainer.

Reference surface: ray.train.ScalingConfig/RunConfig/FailureConfig/
CheckpointConfig (python/ray/train/), DataParallelTrainer
(train/v2/api/data_parallel_trainer.py:66) and the TPU-specific JaxTrainer
(train/v2/jax/jax_trainer.py:20, config.py:40-121).

TPU-first redesign: JaxTrainer's workers form a JAX SPMD gang — rank 0's host
is the jax.distributed coordinator (rendezvous address broadcast through the
worker group exactly like JaxConfig's `_setup_jax_distributed_environment`),
topology-aware placement reserves whole TPU slices, and MEGASCALE env vars
carry cross-slice (DCN) coordination.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

from ray_tpu.train._checkpoint import CheckpointManager
from ray_tpu.train._controller import TrainController, TrainResult
from ray_tpu.train._policies import (
    ElasticScalingPolicy,
    FailurePolicy,
    FixedScalingPolicy,
)


@dataclass
class ScalingConfig:
    """Reference: ray.train.ScalingConfig (+ TPU fields of v2/jax/config.py).

    Setting `elastic_min_workers` makes the run ELASTIC: the group sizes
    to current usable capacity within [elastic_min_workers, num_workers],
    and — when the train fn drives `ctx.elastic.sync()` each step — a
    planned node removal (drain/preemption) with enough survivors resizes
    the live gang instead of tearing it down, re-expanding when capacity
    returns (see train/_elastic.py; knob: `train_live_resize`)."""

    num_workers: int = 1
    resources_per_worker: Dict[str, float] = field(default_factory=dict)
    use_tpu: bool = False
    topology: str = ""  # e.g. "4x4" — reserves whole slices when use_tpu
    accelerator_type: str = ""  # e.g. "v5e"
    elastic_min_workers: Optional[int] = None  # set → elastic scaling

    def policy(self):
        if self.elastic_min_workers is not None:
            return ElasticScalingPolicy(self.elastic_min_workers,
                                        self.num_workers)
        return FixedScalingPolicy(self.num_workers)

    def worker_resources(self) -> Dict[str, float]:
        res = dict(self.resources_per_worker)
        if not res:
            res = {"CPU": 1.0}
        return res


@dataclass
class FailureConfig:
    """Reference: ray.train.FailureConfig."""

    max_failures: int = 0


@dataclass
class CheckpointConfig:
    """Reference: ray.train.CheckpointConfig."""

    num_to_keep: int = 2
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "min"


@dataclass
class RunConfig:
    """Reference: ray.train.RunConfig."""

    name: str = ""
    storage_path: str = ""
    failure_config: FailureConfig = field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = field(default_factory=CheckpointConfig)

    def resolved_name(self) -> str:
        return self.name or f"train-run-{int(time.time())}"

    def resolved_storage(self) -> str:
        return self.storage_path or os.path.join(
            os.path.expanduser("~"), "ray_tpu_results"
        )


class DataParallelTrainer:
    """Runs `train_loop_per_worker` on N gang-scheduled workers.

    Reference: train/v2/api/data_parallel_trainer.py:66. fit() drives the
    controller loop synchronously and returns a TrainResult.
    """

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[dict] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
    ):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()

    def _controller(self) -> TrainController:
        run_name = self.run_config.resolved_name()
        storage = self.run_config.resolved_storage()
        cc = self.run_config.checkpoint_config
        manager = CheckpointManager(
            storage, run_name,
            num_to_keep=cc.num_to_keep,
            metric=cc.checkpoint_score_attribute,
            mode=cc.checkpoint_score_order,
        )
        return TrainController(
            train_fn=self.train_loop_per_worker,
            train_config=self.train_loop_config,
            scaling_policy=self.scaling_config.policy(),
            failure_policy=FailurePolicy(
                self.run_config.failure_config.max_failures
            ),
            resources_per_worker=self.scaling_config.worker_resources(),
            run_name=run_name,
            storage_path=storage,
            checkpoint_manager=manager,
            use_tpu_slices=bool(
                self.scaling_config.use_tpu and self.scaling_config.topology
            ),
            topology=self.scaling_config.topology,
            accelerator_type=self.scaling_config.accelerator_type,
        )

    def fit(self) -> TrainResult:
        result = self._controller().run()
        if result.error is not None:
            raise TrainingFailedError(result.error)
        return result


class TrainingFailedError(RuntimeError):
    """Training exhausted its failure budget (reference: TrainingFailedError)."""


class JaxTrainer(DataParallelTrainer):
    """SPMD JAX training over a TPU gang (reference: v2/jax/jax_trainer.py:20).

    The train loop runs once per host process; call
    `ray_tpu.train.setup_jax_distributed()` first thing inside it to join the
    global mesh (coordinator address + rank/world size are injected by the
    worker group, mirroring _setup_jax_distributed_environment
    (reference: v2/jax/config.py:60-121)).
    """

    def __init__(self, train_loop_per_worker: Callable, **kwargs):
        scaling = kwargs.get("scaling_config") or ScalingConfig()
        if scaling.use_tpu and not scaling.resources_per_worker:
            # one worker process per TPU host, owning all its chips: take
            # the count the hosts advertise, never an assumed one
            import ray_tpu

            per_host = [n["resources"].get("TPU", 0) for n in ray_tpu.nodes()
                        if n["state"] == "ALIVE"]
            chips = min((c for c in per_host if c > 0), default=0)
            if not chips:
                raise ValueError(
                    "ScalingConfig(use_tpu=True) but no alive node "
                    "advertises a TPU resource")
            scaling.resources_per_worker = {"TPU": float(chips)}
        kwargs["scaling_config"] = scaling
        super().__init__(train_loop_per_worker, **kwargs)


def setup_jax_distributed(local_device_count: Optional[int] = None) -> None:
    """Join the run's global JAX mesh from inside a train worker.

    Uses the coordinator/rank env vars injected by the worker group
    (RT_TRAIN_COORDINATOR / RT_TRAIN_RANK / RT_TRAIN_WORLD_SIZE — the same
    contract as MEGASCALE/jax.distributed in the reference). No-op for a
    single-worker run.
    """
    import jax

    world = int(os.environ.get("RT_TRAIN_WORLD_SIZE", "1"))
    if world <= 1:
        return
    coord = os.environ["RT_TRAIN_COORDINATOR"]
    rank = int(os.environ["RT_TRAIN_RANK"])
    kwargs = {}
    if local_device_count is not None:
        kwargs["local_device_ids"] = list(range(local_device_count))
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=world, process_id=rank,
        **kwargs,
    )
    from ray_tpu.tpu.accelerator import check_granted_devices

    check_granted_devices()

"""TPU accelerator manager: detection, visibility, resource naming.

Capability parity with the reference's TPU accelerator plugin (reference:
python/ray/_private/accelerators/tpu.py — device-file chip count :155-258,
TPU_VISIBLE_CHIPS :42, topology validation :96, pod-head resource
`TPU-{type}-head` :345) and the AcceleratorManager ABC (accelerator.py).

Key semantics:
- a chip belongs to ONE process at a time: the node daemon counts chips
  from device files and never loads libtpu; each granted worker is spawned
  seeing exactly its chips (`set_visible_chips_env`) and checks what JAX
  shows it against the grant (`check_granted_devices`);
- a chip is free when its holder is GONE, not when it was signalled: the
  kernel takes seconds to tear down a killed process that had gigabytes
  mapped on its chips, and until then the chip's device file opens with
  EBUSY. The daemon hands a killed worker's chips on only when they open
  (`busy_chip`), and a granted worker waits out a holder it cannot see
  (another cluster's, a run before this one) before it touches JAX
  (`wait_for_chips`);
- TPU resources are named by accelerator version ("TPU-v5e" etc.) when the
  host says which it is (`TPU_ACCELERATOR_TYPE`);
- the FIRST host of a slice additionally exposes `TPU-{pod_type}-head: 1`, the
  hook the slice scheduler gangs on.
"""

from __future__ import annotations

import errno
import glob
import logging
import os
import re
import time
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional

from ray_tpu._private.config import GLOBAL_CONFIG

logger = logging.getLogger(__name__)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
TPU_CHIPS_PER_HOST_BOUNDS_ENV = "TPU_CHIPS_PER_HOST_BOUNDS"
TPU_HOST_BOUNDS_ENV = "TPU_HOST_BOUNDS"
TPU_ACCELERATOR_TYPE_ENV = "TPU_ACCELERATOR_TYPE"  # e.g. "v5litepod-16"
TPU_WORKER_ID_ENV = "TPU_WORKER_ID"
TPU_NAME_ENV = "TPU_NAME"
# set by the node daemon on every worker it spawns with a chip grant: the
# granted chip ids, comma-separated (present even when the grant is the whole
# host and TPU_VISIBLE_CHIPS is therefore left unset)
GRANTED_CHIPS_ENV = "RT_TPU_CHIPS"
# how long a granted worker waits for chips somebody is still letting go of
# before it fails as it would have at once (on a v5e host a killed holder of
# four chips with 5 GB on each was gone after 18 s); under the 120 s an
# actor's creation may take
CHIP_ATTACH_LIMIT_S = 60.0


@dataclass
class TpuInfo:
    generation: str            # "v5e", "v4", ... ("" when the host doesn't say)
    pod_type: str              # "v5e-16" style (accelerator_type normalized)
    topology: str              # "4x4" style when known
    chips_on_host: int
    hosts_in_slice: int
    worker_id: int             # this host's index within the slice
    slice_name: str

    @property
    def resource_name(self) -> str:
        return f"TPU-{self.generation}"

    @property
    def head_resource_name(self) -> str:
        return f"TPU-{self.pod_type}-head"


def _normalize_generation(accel_type: str) -> str:
    gen = accel_type.split("-")[0].lower()
    return {"v5litepod": "v5e", "v5lite": "v5e"}.get(gen, gen)


def count_host_chips(dev_root: str = "/dev") -> int:
    """Chips attached to this host, counted from device files — loading
    libtpu to ask would claim them. Older generations appear as
    `/dev/accel<N>`, vfio-bound ones (v5e and later) as one numbered IOMMU
    group each under `/dev/vfio/` (reference: tpu.py
    get_current_node_num_accelerators)."""
    accel = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    if accel:
        return len(accel)
    try:
        return sum(e.isdigit() for e in os.listdir(
            os.path.join(dev_root, "vfio")))
    except OSError:
        return 0


class TpuAcceleratorManager:
    """Detection + env handling for the node daemon and worker pool."""

    @staticmethod
    def detect() -> Optional[TpuInfo]:
        """What the host has, without touching libtpu: the chip count from
        device files (or the `tpu_chips_per_host` override), generation and
        slice shape from `TPU_ACCELERATOR_TYPE` when the host sets it."""
        chips = GLOBAL_CONFIG.get("tpu_chips_per_host") or count_host_chips()
        if not chips:
            return None
        accel_type = os.environ.get(TPU_ACCELERATOR_TYPE_ENV, "")
        gen = _normalize_generation(accel_type) if accel_type else ""
        num_chips_total = chips
        m = re.match(r".*-(\d+)$", accel_type)
        if m:
            num_chips_total = int(m.group(1))
            # for v2/v3/v4/v5p the accelerator-type suffix counts TensorCores
            # (2 per chip), not chips (reference: tpu.py get_tpu_cores_per_chip
            # semantics, :155-188); v5e/v6e suffixes already count chips
            if gen in ("v2", "v3", "v4", "v5p"):
                num_chips_total = max(1, num_chips_total // 2)
        pod_type = f"{gen}-{num_chips_total}" if gen else ""
        return TpuInfo(
            generation=gen,
            pod_type=pod_type,
            topology=GLOBAL_CONFIG.get("tpu_topology") or os.environ.get(
                "TPU_TOPOLOGY", ""),
            chips_on_host=chips,
            hosts_in_slice=max(1, num_chips_total // chips),
            worker_id=int(os.environ.get(TPU_WORKER_ID_ENV, "0")),
            slice_name=os.environ.get(TPU_NAME_ENV, pod_type),
        )

    @staticmethod
    def node_resources_and_labels(info: Optional[TpuInfo] = None):
        """Resources + labels the node daemon should advertise."""
        info = info or TpuAcceleratorManager.detect()
        if info is None:
            return {}, {}
        resources: Dict[str, float] = {"TPU": float(info.chips_on_host)}
        labels = {"tpu-worker-id": str(info.worker_id)}
        if info.generation:
            resources[info.resource_name] = float(info.chips_on_host)
            if info.worker_id == 0:
                resources[info.head_resource_name] = 1.0
            labels.update({
                "tpu-generation": info.generation,
                "tpu-pod-type": info.pod_type,
                "tpu-slice-name": info.slice_name,
            })
        if info.topology:
            labels["tpu-topology"] = info.topology
        return resources, labels

    @staticmethod
    def set_visible_chips_env(env: Dict[str, str], chip_ids: List[int],
                              chips_per_host: int) -> None:
        """Restrict a worker process to specific chips (reference: tpu.py:42-55).

        With all chips granted, the env vars are left unset so libtpu owns the
        full host under whatever bounds the machine itself exports. For a
        subset, libtpu must also be told that this process's "host" is that
        small: tried on a four-chip v5e host with libtpu 0.0.34, the three
        variables below let four one-chip processes, or two two-chip
        processes, hold their chips at the same time (each numbers its own
        devices from 0).
        """
        if len(chip_ids) >= chips_per_host:
            return
        bounds = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}.get(len(chip_ids))
        if bounds is None:
            raise ValueError(
                f"cannot show a worker {len(chip_ids)} of {chips_per_host} "
                f"chips: sub-host grants are 1, 2 or 4 chips")
        env[TPU_VISIBLE_CHIPS_ENV] = ",".join(str(c) for c in chip_ids)
        env[TPU_CHIPS_PER_HOST_BOUNDS_ENV] = bounds
        env[TPU_HOST_BOUNDS_ENV] = "1,1,1"


def worker_platform(granted: bool) -> Optional[str]:
    """The JAX_PLATFORMS a spawned worker must run under, or None to leave
    the daemon's own setting alone. A worker with a chip grant gets the TPU
    backend or dies (JAX raises when a named platform fails to initialise —
    it cannot carry on on the CPU); a worker without one is pinned to the
    CPU so it can never take a chip. A daemon environment that names
    platforms and leaves TPU out (tier-1's `cpu` with fake TPU resources)
    wins."""
    explicit = os.environ.get("JAX_PLATFORMS", "")
    if explicit and "tpu" not in explicit.split(","):
        return None
    return "tpu" if granted else "cpu"


def granted_chips() -> List[str]:
    """Chip ids the node daemon granted this worker ([] when none)."""
    return [c for c in os.environ.get(GRANTED_CHIPS_ENV, "").split(",") if c]


def chip_device_files(chips: Iterable, dev_root: str = "/dev") -> List[str]:
    """The device files of chip ids (the daemon's numbering, which is
    libtpu's `TPU_VISIBLE_CHIPS`): chip n is IOMMU group `/dev/vfio/<n>` on a
    vfio-bound host (tried on a four-chip v5e host: four one-chip processes
    made exactly their own group's file busy), `/dev/accel<n>` on an older
    one. A file that is not there is left out."""
    accel = bool(glob.glob(os.path.join(dev_root, "accel[0-9]*")))
    paths = [os.path.join(dev_root, f"accel{int(c)}") if accel
             else os.path.join(dev_root, "vfio", str(int(c))) for c in chips]
    return [p for p in paths if os.path.exists(p)]


def busy_chip(paths: Iterable[str],
              opener: Callable[[str, int], int] = os.open) -> Optional[str]:
    """The first device file that another process still holds (it opens
    with EBUSY, which is what libtpu's start fails on), or None. Opened
    and closed at once; any other error is for JAX to report."""
    for path in paths:
        try:
            os.close(opener(path, os.O_RDWR))
        except OSError as e:
            if e.errno == errno.EBUSY:
                return path
    return None


def wait_for_chips(paths: Iterable[str], limit_s: float = CHIP_ATTACH_LIMIT_S,
                   opener: Callable[[str, int], int] = os.open,
                   sleep: Callable[[float], None] = time.sleep) -> float:
    """Wait until none of the device files is held any more; returns the
    seconds waited (0.0 when nobody was met). Raises RuntimeError naming the
    device that is still busy at the limit."""
    paths, t0 = list(paths), time.monotonic()
    held = busy_chip(paths, opener)
    if held is None:
        return 0.0
    while held is not None:
        waited = time.monotonic() - t0
        if waited >= limit_s:
            raise RuntimeError(
                f"TPU chip {held} is still held by another process after "
                f"{waited:.1f} s (chip_attach_wait_s; limit {limit_s:.0f} s)")
        sleep(0.25)
        held = busy_chip(paths, opener)
    return time.monotonic() - t0


def check_granted_devices() -> None:
    """Called by a worker that builds a model, before anything is built: a
    worker the daemon pinned to the TPU platform waits until its chips'
    last holder has let go of them (`wait_for_chips`), and must then see
    only TPU chips, exactly as many as it was granted. Raises RuntimeError
    otherwise. (On a CPU-pinned cluster with fake TPU resources there is
    nothing to check, and no backend is touched.)"""
    if os.environ.get("JAX_PLATFORMS") != "tpu":
        return
    from ray_tpu.util.metrics import get_or_create_counter

    granted = granted_chips()
    waited = wait_for_chips(chip_device_files(granted))
    get_or_create_counter(
        "rt_chip_attach_wait_s",
        "Seconds granted workers waited for chips their last holder had "
        "not let go of yet.").inc(waited)
    logger.info("chips %s attached: chip_attach_wait_s=%.3f",
                ",".join(granted), waited)
    import jax

    devices = jax.local_devices()
    if any(d.platform != "tpu" for d in devices) or (
            len(devices) != len(granted)):
        raise RuntimeError(
            f"worker was granted TPU chips {granted} but JAX shows "
            f"{[(d.platform, d.id) for d in devices]} "
            f"({TPU_VISIBLE_CHIPS_ENV}="
            f"{os.environ.get(TPU_VISIBLE_CHIPS_ENV)!r})")


def chip_options() -> Dict[str, Dict[str, float]]:
    """Actor `.options()` for an actor that builds a model: it asks for its
    chip whenever the cluster advertises any, so it lands in a process that
    sees exactly that chip. On a cluster with no TPU resource (CPU tests)
    it asks for nothing."""
    import ray_tpu

    if ray_tpu.cluster_resources().get("TPU", 0) > 0:
        return {"resources": {"TPU": 1.0}}
    return {}

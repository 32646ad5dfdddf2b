"""JAX/TPU profiler capture across the cluster.

Reference surface: the dashboard's JAX capture endpoint
(dashboard/modules/reporter/jax_profile_manager.py:11). Capture writes an
XPlane/perfetto trace directory the driver can fetch or inspect, either
from a fresh task pinned to a node (`node_capture_task`, the dashboard's
route: a new worker's own idle runtime) or from inside a live actor's own
process (`capture_in_actor`): only the process that holds a chip can
trace it.
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import List, Optional

import ray_tpu


def capture_local(logdir: str, duration_s: float = 2.0,
                  workload=None) -> str:
    """Trace this process's JAX activity for duration_s (or around
    `workload()` if given); returns the trace dir."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    try:
        if workload is not None:
            workload()
        else:
            time.sleep(duration_s)
    finally:
        jax.profiler.stop_trace()
    return logdir


def _capture_here(logdir: Optional[str], duration_s: float):
    """Capture this process's JAX runtime trace. logdir=None creates a
    temp dir ON THE TARGET (a dashboard-side path would be meaningless on
    another node). Returns (logdir, files)."""
    if logdir is None:
        import tempfile

        logdir = tempfile.mkdtemp(prefix="rt_jaxprof_")
    capture_local(logdir, duration_s)
    out = []
    for root, _dirs, files in os.walk(logdir):
        out.extend(os.path.join(root, f) for f in files)
    return logdir, out


_capture_task = ray_tpu.remote(_capture_here)


async def _capture_in_actor(_instance, logdir: Optional[str],
                            duration_s: float):
    """`__rt_call__` body: the capture runs on a thread of the actor's
    process, so the actor's event loop (an engine's decode loop) keeps
    running while it is traced and while `stop_trace` writes the file."""
    return await asyncio.to_thread(_capture_here, logdir, duration_s)


def node_capture_task(node_id_hex: str):
    """The capture task pinned to `node_id_hex` (the dashboard's
    /api/jax_profile). It runs in a NEW worker on that node: it sees that
    worker's own (idle) JAX runtime, and no chip that another process
    holds. To trace an engine or a train worker, use `capture_in_actor`."""
    from ray_tpu._private.protocol import SchedulingStrategy

    return _capture_task.options(
        scheduling_strategy=SchedulingStrategy(
            kind="NODE_AFFINITY", node_id=node_id_hex, soft=False),
    )


def capture_in_actor(actor, logdir: Optional[str] = None,
                     duration_s: float = 2.0) -> List[str]:
    """Capture a JAX profile inside a live actor's own process, the one
    that holds its chip: device operations, the programs by name and the
    process's `TraceAnnotation`s (the engine loop's `engine:*` phases)
    land on one clock. Returns trace file paths on the actor's node."""
    _dir, files = ray_tpu.get(
        actor.__rt_call__.remote(_capture_in_actor, logdir, duration_s),
        timeout=duration_s + 120)
    return files


__all__ = ["capture_in_actor", "capture_local", "node_capture_task"]

"""A chip is free when its holder is gone: the daemon hands a killed worker's
chips on only when its process has been reaped, `ray_tpu.shutdown()` leaves
no process of the session behind, and a granted worker waits out a chip
that somebody is still letting go of before it touches JAX."""

import asyncio
import errno
import os
import time

import pytest

import ray_tpu
from ray_tpu.tpu import accelerator


class Proc:
    """Stand-in for a worker's Popen: signalled, and gone when told so."""

    pid = 2 ** 22 + 12345  # above any pid this host hands out

    def __init__(self):
        self.code = None

    def poll(self):
        return self.code


def chip_daemon(chips):
    from ray_tpu._private.node_daemon import NodeDaemon

    d = NodeDaemon.__new__(NodeDaemon)
    d.workers, d.idle_by_job, d._creating_actors = {}, {}, {}
    d._tpu_free_chips, d._tpu_releasing = list(range(chips)), []
    d._report_worker_death_quiet = lambda w, reason="": asyncio.sleep(0)
    return d


def chip_worker(d, proc, chips):
    from ray_tpu._private.ids import WorkerID
    from ray_tpu._private.node_daemon import W_ACTOR, WorkerHandle

    w = WorkerHandle(WorkerID.from_random(), proc, b"job")
    w.state = W_ACTOR
    w.tpu_chips = tuple(chips)
    d.workers[w.worker_id.binary()] = w
    del d._tpu_free_chips[:len(chips)]
    return w


def test_killed_workers_chips_come_back_when_it_is_reaped():
    async def scenario():
        d = chip_daemon(4)
        proc = Proc()
        w = chip_worker(d, proc, (0, 1, 2, 3))
        d._kill_worker_proc(w, "test")
        assert w.worker_id.binary() not in d.workers
        # signalled, not gone: nothing to hand on, and a grant waits
        d._reclaim_chips()
        assert d._tpu_free_chips == []
        grant = asyncio.ensure_future(d._alloc_chips(2))
        await asyncio.sleep(0.2)
        assert not grant.done() and d._tpu_free_chips == []
        proc.code = -9
        assert await asyncio.wait_for(grant, 5) == [0, 1]
        assert d._tpu_free_chips == [2, 3] and d._tpu_releasing == []

    asyncio.run(scenario())


def test_worker_that_died_on_its_own_frees_its_chips_at_once():
    d = chip_daemon(2)
    proc = Proc()
    proc.code = 1
    w = chip_worker(d, proc, (0, 1))
    d._forget_worker(w)
    assert d._tpu_free_chips == [0, 1] and d._tpu_releasing == []


def test_reaped_holder_whose_device_file_is_still_busy(monkeypatch):
    """Reaped is not enough where the device file still refuses to open."""
    d = chip_daemon(1)
    proc = Proc()
    proc.code = -9
    w = chip_worker(d, proc, (0,))
    held = ["/dev/vfio/0"]
    monkeypatch.setattr(accelerator, "chip_device_files", lambda chips: held)
    monkeypatch.setattr(accelerator, "busy_chip",
                        lambda paths: paths[0] if paths else None)
    d._forget_worker(w)
    assert d._tpu_free_chips == []
    held.clear()
    d._reclaim_chips()
    assert d._tpu_free_chips == [0]


def session_processes(root_pids):
    from ray_tpu._private import node as node_mod

    return {p for r in root_pids for p in [r, *node_mod._descendants(r)]}


def test_shutdown_leaves_no_process_of_the_session():
    from ray_tpu._private import node as node_mod
    from ray_tpu._private.worker import _context

    ray_tpu.init(num_cpus=2, resources={"TPU": 2})
    try:
        @ray_tpu.remote
        class Holder:
            def pid(self):
                return os.getpid()

        a = Holder.options(resources={"TPU": 2.0}).remote()
        holder = ray_tpu.get(a.pid.remote(), timeout=60)
        pids = session_processes([p.pid for p in _context.owned_processes])
        groups = {os.getpgid(p) for p in pids}
        assert holder in pids and len(groups) > 2  # workers lead their own
    finally:
        ray_tpu.shutdown()
    # by process group, not by name: nothing alive in any of them, the
    # moment shutdown() returns
    left = []
    for g in groups:
        try:
            os.killpg(g, 0)
        except ProcessLookupError:
            continue
        left += [p for p in pids if not node_mod._gone(p)]
    assert not left


def test_kill_process_sweeps_what_a_killed_daemon_left():
    """A daemon killed outright never stops its workers: whoever killed it
    does, before returning."""
    import subprocess
    import sys

    from ray_tpu._private import node as node_mod

    parent = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys, time\n"
         "subprocess.Popen([sys.executable, '-c', 'import time; time.sleep(600)'],"
         " start_new_session=True)\n"
         "print('up', flush=True); time.sleep(600)"],
        stdout=subprocess.PIPE, start_new_session=True)
    assert parent.stdout.readline().strip() == b"up"
    (child,) = node_mod._descendants(parent.pid)
    node_mod.kill_process(parent, force=True)
    assert parent.poll() is not None and node_mod._gone(child)


class Opener:
    """`os.open` for device files that are busy for the first `busy` tries."""

    def __init__(self, busy):
        self.busy, self.calls = busy, 0

    def __call__(self, path, flags):
        self.calls += 1
        if self.calls <= self.busy:
            raise OSError(errno.EBUSY, "Device or resource busy", path)
        return os.open(os.devnull, os.O_RDONLY)


def test_attach_wait_reports_its_seconds_and_returns():
    slept = []
    opener = Opener(busy=2)
    waited = accelerator.wait_for_chips(
        ["/dev/vfio/0"], opener=opener,
        sleep=lambda s: (slept.append(s), time.sleep(0.01)))
    assert opener.calls == 3 and len(slept) == 2
    assert 0.02 <= waited < 5
    # nobody met: exactly 0.0, and one open a file
    opener = Opener(busy=0)
    assert accelerator.wait_for_chips(["/dev/vfio/0", "/dev/vfio/1"],
                                      opener=opener) == 0.0
    assert opener.calls == 2


def test_attach_wait_raises_at_the_limit_naming_the_device():
    opener = Opener(busy=10 ** 9)
    with pytest.raises(RuntimeError, match=r"/dev/vfio/3.*chip_attach_wait_s"):
        accelerator.wait_for_chips(["/dev/vfio/3"], limit_s=0.05, opener=opener,
                                   sleep=lambda s: time.sleep(0.01))
    assert opener.calls >= 2


def test_other_open_errors_are_left_to_jax():
    def denied(path, flags):
        raise OSError(errno.EACCES, "Permission denied", path)

    assert accelerator.busy_chip(["/dev/vfio/0"], denied) is None


def test_chip_device_files_by_id(tmp_path):
    (tmp_path / "vfio").mkdir()
    for name in ("0", "1", "2", "3", "vfio"):
        (tmp_path / "vfio" / name).touch()
    root = str(tmp_path)
    assert accelerator.chip_device_files(["2", "3"], root) == [
        f"{root}/vfio/2", f"{root}/vfio/3"]
    assert accelerator.chip_device_files([7], root) == []
    (tmp_path / "accel0").touch()
    assert accelerator.chip_device_files([0, 1], root) == [f"{root}/accel0"]


def test_granted_worker_waits_before_it_touches_jax(monkeypatch):
    """`check_granted_devices` is the one attach point (train workers and
    engines call it): the wait sits in front of JAX and counts its seconds."""
    import jax

    from ray_tpu.util import metrics

    order = []
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv(accelerator.GRANTED_CHIPS_ENV, "0,1")
    monkeypatch.setattr(accelerator, "chip_device_files",
                        lambda chips: [f"/dev/vfio/{c}" for c in chips])
    monkeypatch.setattr(accelerator, "wait_for_chips",
                        lambda paths: (order.append(("wait", paths)), 1.5)[1])

    class Dev:
        platform = "tpu"
        id = 0

    monkeypatch.setattr(jax, "local_devices",
                        lambda: (order.append(("jax",)), [Dev(), Dev()])[1])
    before = sum(s["value"] for s in metrics.snapshot_all()
                 if s["name"] == "rt_chip_attach_wait_s")
    accelerator.check_granted_devices()
    assert order == [("wait", ["/dev/vfio/0", "/dev/vfio/1"]), ("jax",)]
    after = sum(s["value"] for s in metrics.snapshot_all()
                if s["name"] == "rt_chip_attach_wait_s")
    assert after - before == 1.5

"""chip_smoke.py off the chip: it must refuse to pass without a TPU, fail when
a phase fails, and its phases must drive the one-process-per-chip machinery
(chip grants, platform pinning, compile-cache placement) end to end at the
tiny preset on a CPU-pinned cluster with fake TPU resources."""

import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import default_worker
from ray_tpu.tpu import accelerator

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_exits_nonzero_without_a_tpu():
    """(a) the chipless sandbox: a message naming the missing TPU, no
    result line, a non-zero exit code."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--phase",
         "serve"], capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_failing_phase_fails_the_run(monkeypatch):
    """(c) any phase raising makes main() return non-zero (it is the
    process's exit code) and print no summary."""
    def boom(chips, preset):
        raise RuntimeError("phase blew up")

    monkeypatch.setitem(chip_smoke.PHASES, "serve", boom)
    assert chip_smoke.main(["--preset", "tiny", "--phase", "serve"]) == 1
    assert not ray_tpu.is_initialized()


def test_last_stdout_line_is_the_result_and_nothing_else(monkeypatch, capsys):
    """The chip check reads the last line of stdout: one JSON object with
    exactly "ok" and "device" {"platform", "kind", "count"}. Everything else
    (phases, "claim": null) goes on the summary line before it."""
    import json

    def passed(chips, preset):
        return {"phase": "serve", "ok": True, "platform": "cpu",
                "device_kind": "cpu", "device_count": chips}

    monkeypatch.setitem(chip_smoke.PHASES, "serve", passed)
    # the test process, unlike a chip_smoke.py driver, may hold a (CPU)
    # backend from an earlier test; main() would rightly fail on that
    from jax._src import xla_bridge

    monkeypatch.setattr(xla_bridge, "backends_are_initialized", lambda: False)
    assert chip_smoke.main(["--preset", "tiny", "--phase", "serve"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 2}}
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and summary["phases"] == {"serve": "ok"}


def test_worker_platform_pinning(monkeypatch):
    """Granted -> tpu, ungranted -> cpu, unless the daemon's environment
    names platforms without TPU (tier-1's cpu), which wins."""
    for env in (None, "tpu", "tpu,cpu"):
        if env is None:
            monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        else:
            monkeypatch.setenv("JAX_PLATFORMS", env)
        assert accelerator.worker_platform(granted=True) == "tpu"
        assert accelerator.worker_platform(granted=False) == "cpu"
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert accelerator.worker_platform(granted=True) is None
    assert accelerator.worker_platform(granted=False) is None


def test_chips_counted_from_device_files(tmp_path):
    assert accelerator.count_host_chips(str(tmp_path)) == 0
    (tmp_path / "vfio").mkdir()
    for name in ("0", "1", "2", "3", "vfio"):
        (tmp_path / "vfio" / name).touch()
    assert accelerator.count_host_chips(str(tmp_path)) == 4
    (tmp_path / "accel0").touch()
    assert accelerator.count_host_chips(str(tmp_path)) == 1


def test_default_compile_cache_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert default_worker.place_compile_cache() == os.path.join(
        REPO, ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert default_worker.place_compile_cache() == "/somewhere/else"


@pytest.fixture
def fake_two_chip_cluster(monkeypatch, tmp_path):
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    ray_tpu.init(num_cpus=8, resources={"TPU": 2})
    yield cache
    from ray_tpu import serve

    serve.shutdown()
    ray_tpu.shutdown()


def test_serve_phase_tiny_one_engine_per_chip(fake_two_chip_cluster):
    """(b) the serve phase at the tiny preset: two engines behind one
    route hold disjoint chips, an ungranted worker is pinned to the CPU,
    and the driver's compile-cache directory reaches workers unchanged."""
    result = chip_smoke.serve_phase(2, chip_smoke.TINY)
    assert result["ok"] and result["engine_processes"] == 2
    assert sorted(result["granted_chips"]) == ["0", "1"]
    assert result["requests"] >= 8
    assert result["prefix_cache"]["block_hits"] > 0

    @ray_tpu.remote
    class EnvReader:
        def read(self):
            return {k: os.environ.get(k) for k in (
                "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "RT_TPU_CHIPS",
                "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
                "JAX_COMPILATION_CACHE_DIR")}

    ungranted = EnvReader.remote()
    env = ray_tpu.get(ungranted.read.remote(), timeout=60)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["RT_TPU_CHIPS"] is None and env["TPU_VISIBLE_CHIPS"] is None
    assert env["JAX_COMPILATION_CACHE_DIR"] == fake_two_chip_cluster
    ray_tpu.kill(ungranted)


@pytest.mark.mid
def test_train_phase_tiny_fsdp_over_granted_chips(fake_two_chip_cluster):
    """The train phase at the tiny preset: JaxTrainer sizes its one worker
    to the host's chips, the fsdp mesh spans them, and every kernel case
    runs (interpreted) against its reference."""
    result = chip_smoke.train_phase(2, chip_smoke.TINY)
    assert result["ok"] and result["mesh"] == {"fsdp": 2}
    assert result["losses"][-1] < result["losses"][1]
    assert len(result["kernels"]) == 7


def test_host_stall_is_not_node_silence():
    """Attaching four chips at once freezes a v5e host for 10-12 s, longer
    than health_check_timeout_s: time the control store itself was not
    running must not count against a node; real silence still does."""
    import asyncio
    import time

    from ray_tpu._private import protocol as pb
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu._private.control_store import ControlStore
    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.protocol import NodeInfo, ResourceSet

    GLOBAL_CONFIG.apply_system_config(
        {"health_check_period_s": 0.05, "health_check_timeout_s": 0.4})

    async def run():
        cs = ControlStore()
        wire = NodeInfo(node_id=NodeID.from_random(), address="127.0.0.1:1",
                        object_store_name="none",
                        resources=ResourceSet({"CPU": 1}), labels={}).to_wire()
        nid = wire["node_id"]
        await cs.rpc_register_node(0, {"node": wire})
        health = asyncio.ensure_future(cs._health_loop())
        try:
            await asyncio.sleep(0.1)
            time.sleep(0.8)  # the whole process frozen, this loop included
            await asyncio.sleep(0.1)
            assert cs.nodes[nid].state == pb.NODE_ALIVE
            await asyncio.sleep(0.6)  # the loop runs, the node says nothing
            assert cs.nodes[nid].state == pb.NODE_DEAD
        finally:
            cs._stopped = True
            health.cancel()

    asyncio.run(run())

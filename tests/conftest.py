"""Test harness: runs JAX on a virtual 8-device CPU mesh (no TPU needed),
mirroring the reference's fake-multi-node strategy for cluster tests
(reference: python/ray/tests/conftest.py:651,734 and
python/ray/autoscaler/_private/fake_multi_node/node_provider.py).
"""

import os

# Tests run on a virtual 8-device CPU mesh. Setting the env before the first
# `import jax` is enough, and spawned daemons/workers inherit it (an explicit
# JAX_PLATFORMS in the daemon's env wins over the chip-grant pinning).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "mid: multi-second cluster/chaos tests — excluded from "
        "tier-1 like slow, but runnable as a middle tier via -m mid")


def pytest_collection_modifyitems(config, items):
    # `mid` implies `slow` so the unchanged tier-1 line (-m 'not slow')
    # skips the middle tier too; `-m mid` still selects exactly that tier
    # and `-m 'slow and not mid'` the long tail.
    for item in items:
        if (item.get_closest_marker("mid")
                and not item.get_closest_marker("slow")):
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _reset_global_config():
    from ray_tpu._private import chaos
    from ray_tpu._private.config import GLOBAL_CONFIG
    from ray_tpu.util.metrics import reset_registry

    yield
    GLOBAL_CONFIG.reset()
    chaos.reset()
    # metric registry isolation: a test re-declaring a name with different
    # tag_keys/boundaries must not trip over another test's registration
    reset_registry()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """Chaos-harness auto-dump: a FAILING chaos-soak scenario dumps the
    flight-recorder rings of every involved process (driver, control
    store, daemons, workers) to a temp dir before teardown destroys the
    cluster — the post-mortem starts from recorded control-plane events,
    not from log archaeology."""
    outcome = yield
    rep = outcome.get_result()
    if rep.when != "call" or not rep.failed:
        return
    if "chaos" not in item.nodeid:
        return
    import re
    import tempfile

    try:
        from ray_tpu.util.state import dump_flight_recorder

        safe = re.sub(r"[^A-Za-z0-9_.-]+", "_", item.nodeid)[-80:]
        dest = os.path.join(tempfile.gettempdir(), f"rt_flight_{safe}")
        dump = dump_flight_recorder(dest)
        paths = [v.get("path") for v in dump.values()
                 if isinstance(v, dict) and v.get("path")]
        print(f"\n[chaos] flight recorder auto-dump: {len(paths)} ring(s) "
              f"written under {dest}")
    except Exception as e:  # noqa: BLE001 — the cluster may be fully dead
        print(f"\n[chaos] flight recorder auto-dump failed: {e!r}")

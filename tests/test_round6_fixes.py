"""Regression tests for the round-6 advisor fixes:

- Dataset.limit(): row-count-changing ops chained after limit never see
  rows past the global budget (stream-order fence, ADVICE r5 #1)
- borrow reaper: borrows dropped only on authoritative control-store death
  records, never on ping timeouts alone (ADVICE r5 #2)
- compiled-DAG teardown: rings close before unpin; rpc_chan_write
  re-checks registration under the per-edge lock (ADVICE r5 #3)
- read_sql range partitioning: numeric-bound + identifier validation
  (ADVICE r5 #4)
- runtime_env: unknown fields fail submission instead of silently
  dropping (ADVICE r5 #5)
"""

import asyncio

import pytest

import ray_tpu


@pytest.fixture(scope="module")
def ray_init():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# limit() stream-order budget (ADVICE r5 #1)
# ---------------------------------------------------------------------------


def test_limit_then_filter_never_sees_extra_rows(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(20)), parallelism=2)  # 2 blocks x 10 rows
    out = ds.limit(5).filter(lambda x: x % 2 == 0)
    # first 5 rows are 0..4 -> evens 0,2,4; the old per-block cap + surface
    # cut returned evens drawn from rows 5..9 of the second block too
    assert out.take_all() == [0, 2, 4]
    assert out.count() == 3


def test_limit_then_flat_map_budget(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(12)), parallelism=3)
    out = ds.limit(4).flat_map(lambda x: [x, x])
    assert out.take_all() == [0, 0, 1, 1, 2, 2, 3, 3]


def test_limit_then_map_stays_fused_and_correct(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(10)), parallelism=2)
    assert ds.limit(3).map(lambda x: x + 100).take_all() == [100, 101, 102]


def test_limit_chain_and_materialize(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(30)), parallelism=3)
    out = ds.limit(10).filter(lambda x: x % 2 == 0).limit(2)
    assert out.take_all() == [0, 2]
    m = ds.limit(5).filter(lambda x: x >= 2).materialize()
    assert m.take_all() == [2, 3, 4]


def test_materialize_keeps_trailing_limit_after_fence(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(30)), parallelism=3)
    out = ds.limit(10).filter(lambda x: x % 2 == 0).limit(2)
    # direct materialize() must honor the trailing limit GLOBALLY, not as a
    # per-block cap (code-review finding on the fence's materialize branch)
    assert out.materialize().take_all() == [0, 2]


def test_filter_then_limit_budget_applies_to_filtered_stream(ray_init):
    from ray_tpu.data import from_items

    ds = from_items(list(range(20)), parallelism=2)
    assert ds.filter(lambda x: x % 2 == 0).limit(3).take_all() == [0, 2, 4]


# ---------------------------------------------------------------------------
# borrow reaper gated on authoritative death records (ADVICE r5 #2)
# ---------------------------------------------------------------------------


class _ReaperHarness:
    """Binds the production _borrow_reaper_loop to a stub CoreWorker whose
    ping always fails, with a scriptable control-store verdict."""

    def __init__(self, verdict):
        from ray_tpu._private.core_worker import CoreWorker

        self._closed = False
        self.dropped = []
        self.lookups = 0
        self._owner_clients = {}
        self.verdict = verdict
        harness = self

        class _Refs:
            def borrower_addresses(self):
                return {"10.0.0.9:1"}

            def drop_borrower_process(self, addr):
                harness.dropped.append(addr)
                return 1

        self.ref_counter = _Refs()

        class _Control:
            async def call(self, method, payload, timeout=None):
                assert method == "check_worker_liveness"
                harness.lookups += 1
                return {"dead": harness.verdict, "known": True}

        self.control = _Control()
        self._loop = CoreWorker._borrow_reaper_loop.__get__(self)

    async def _owner_client(self, addr):
        raise ConnectionError("borrower unreachable (stalled or dead)")


def _run_reaper(verdict, cycles):
    async def scenario():
        from ray_tpu._private.config import GLOBAL_CONFIG

        GLOBAL_CONFIG.apply_system_config({
            "borrow_reaper_period_s": 0.01,
            "borrow_reaper_strikes": 2,
        })
        h = _ReaperHarness(verdict)
        task = asyncio.ensure_future(h._loop())
        await asyncio.sleep(0.01 * cycles)
        h._closed = True
        task.cancel()
        try:
            await task
        except asyncio.CancelledError:
            pass
        return h

    return asyncio.run(scenario())


def test_stalled_but_alive_borrower_keeps_borrows():
    # pings fail every cycle, but the control store says "not dead":
    # borrows must never drop — this is exactly the GIL-stalled borrower
    h = _run_reaper(verdict=False, cycles=30)
    assert h.lookups >= 1, "ping failures never triggered a lookup"
    assert h.dropped == []


def test_recorded_death_drops_borrows():
    h = _run_reaper(verdict=True, cycles=30)
    assert h.dropped, "authoritatively dead borrower was never reaped"


def test_a_release_inside_the_counters_own_lock_is_handed_to_the_loop():
    """ROADMAP C10: the cyclic GC can run an `ObjectRef.__del__` on a thread
    that is inside one of `ReferenceCounter`'s own critical sections; with a
    blocking acquire of the plain lock that thread never came back (tier-1
    hung in `add_local` -> `__del__` -> `remove_local` now and then)."""
    from ray_tpu._private.core_worker import ObjectRef, ReferenceCounter
    from ray_tpu._private.ids import ObjectID

    class _Worker:
        later, freed = [], []

        def schedule(self, coro):
            self.later.append(coro)

        def owns(self, ref):
            return True

        async def free_owned_object(self, oid):
            self.freed.append(oid)

    cw = _Worker()
    refs = ReferenceCounter(cw)
    ref = ObjectRef(ObjectID.from_random(), "owner:1", b"w", _register=False)
    refs.add_local(ref)
    refs.add_local(ref)
    with refs._lock:                # as the GC finds it inside add_local
        refs.remove_local(ref)      # must come back
    assert refs.local_counts[ref.binary()] == 2 and len(cw.later) == 1
    asyncio.run(cw.later.pop())     # the loop's turn
    assert refs.local_counts[ref.binary()] == 1 and not cw.freed
    refs.remove_local(ref)          # the lock is free: decremented in place
    assert ref.binary() not in refs.local_counts
    asyncio.run(cw.later.pop())
    assert cw.freed == [ref.object_id()]


def test_control_store_worker_liveness_records():
    from ray_tpu._private.control_store import ControlStore
    from ray_tpu._private import protocol as pb
    from ray_tpu._private.ids import NodeID

    async def scenario():
        cs = ControlStore()
        nid = NodeID.from_random()
        cs.nodes[nid.binary()] = pb.NodeInfo(
            node_id=nid, address="n:1", object_store_name="s",
            resources=pb.ResourceSet({"CPU": 1}))
        await cs.rpc_register_worker(0, {
            "worker_id": b"w" * 16, "address": "10.0.0.9:1",
            "node_id": nid.hex(),
        })
        alive = await cs.rpc_check_worker_liveness(0, {"address": "10.0.0.9:1"})
        assert alive == {"known": True, "dead": False}
        unknown = await cs.rpc_check_worker_liveness(0, {"address": "nowhere:9"})
        assert unknown["dead"] is False and unknown["known"] is False
        # explicit worker-death report
        await cs.rpc_report_worker_death(0, {"worker_id": b"w" * 16})
        dead = await cs.rpc_check_worker_liveness(0, {"address": "10.0.0.9:1"})
        assert dead["dead"] is True
        # node death marks every address registered on the node
        await cs.rpc_register_worker(0, {
            "worker_id": b"x" * 16, "address": "10.0.0.9:2",
            "node_id": nid.hex(),
        })
        await cs._mark_node_dead(nid.binary(), "test")
        dead2 = await cs.rpc_check_worker_liveness(0, {"address": "10.0.0.9:2"})
        assert dead2["dead"] is True

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# compiled-DAG teardown race (ADVICE r5 #3)
# ---------------------------------------------------------------------------


def test_closed_ring_fails_writers_fast(ray_init):
    """rt_chan_close must make writes fail fast (EOFError), including
    writers parked on a full ring — the teardown half of the race fix."""
    from ray_tpu._private.core_worker import get_core_worker
    from ray_tpu._private.ids import ObjectID
    from ray_tpu.experimental.channel import ShmChannel

    store = get_core_worker().store
    oid = ObjectID.from_random()
    ch = ShmChannel(store, oid, creator=True, nslots=2, slot_size=1024)
    try:
        ch.write_bytes(b"a")
        ch.close()
        with pytest.raises(EOFError):
            ch.write_bytes(b"b", timeout=5)
        with pytest.raises(EOFError):
            ch.reserve_view(4, timeout=5)
        # reader still drains buffered slots, then sees EOF
        assert ch.read_bytes(timeout=5) == b"a"
        with pytest.raises(EOFError):
            ch.read_bytes(timeout=5)
    finally:
        ch.unpin()
        store.delete(oid)


def test_chan_write_rechecks_registration_under_lock(ray_init):
    """An rpc_chan_write that raced past the registry lookup must notice
    the teardown unregistration under the per-edge lock and bail without
    touching the (now unpinned) chan."""
    from ray_tpu._private.core_worker import get_core_worker

    cw = get_core_worker()

    class _Chan:
        def __init__(self):
            self.writes = 0

        def write_bytes(self, payload, timeout=None):
            self.writes += 1

    async def scenario():
        chan = _Chan()
        cw.register_dag_channel("dagX", "e0", chan)
        key = ("dagX", "e0")
        lock = cw._dag_channel_locks.setdefault(key, asyncio.Lock())
        await lock.acquire()  # simulate an in-flight write holding the lock
        write = asyncio.ensure_future(cw.rpc_chan_write(0, {
            "dag_id": "dagX", "edge": "e0", "payload": b"p", "seq": 0,
            "open_timeout": 1, "timeout": 1,
        }))
        await asyncio.sleep(0.05)  # write is parked on the lock
        # teardown: quiesce waits for the lock, so run unregister directly
        cw.unregister_dag_channel("dagX", "e0")
        lock.release()
        reply = await write
        assert reply == {"error": "no_such_channel"}
        assert chan.writes == 0  # the unpinned chan was never touched

    cw.run_sync(scenario())


def test_quiesce_waits_for_inflight_lock(ray_init):
    from ray_tpu._private.core_worker import get_core_worker

    cw = get_core_worker()

    async def scenario():
        cw.register_dag_channel("dagY", "e1", object())
        key = ("dagY", "e1")
        lock = cw._dag_channel_locks.setdefault(key, asyncio.Lock())
        await lock.acquire()
        q = asyncio.ensure_future(cw.quiesce_dag_channel("dagY", "e1"))
        await asyncio.sleep(0.05)
        assert not q.done()  # must not unregister while a writer holds it
        assert key in cw._dag_channels
        lock.release()
        await q
        assert key not in cw._dag_channels

    cw.run_sync(scenario())


# ---------------------------------------------------------------------------
# read_sql hardening (ADVICE r5 #4)
# ---------------------------------------------------------------------------


def test_read_sql_rejects_bad_bounds_and_identifiers():
    from ray_tpu.data.datasource import read_sql

    factory = object  # never called: validation fires first
    with pytest.raises(TypeError, match="numeric"):
        read_sql("SELECT * FROM t", factory, parallelism=2,
                 partition_column="ts", lower_bound="2020-01-01",
                 upper_bound="2021-01-01")
    with pytest.raises(ValueError, match="identifier"):
        read_sql("SELECT * FROM t", factory, parallelism=2,
                 partition_column="id; DROP TABLE t", lower_bound=0,
                 upper_bound=10)
    with pytest.raises(ValueError, match="upper_bound"):
        read_sql("SELECT * FROM t", factory, parallelism=2,
                 partition_column="id", lower_bound=10, upper_bound=0)


def test_read_sql_range_partition_still_works(ray_init):
    import sqlite3
    import tempfile

    from ray_tpu.data.datasource import read_sql

    with tempfile.NamedTemporaryFile(suffix=".db") as f:
        conn = sqlite3.connect(f.name)
        conn.execute("CREATE TABLE t (id INTEGER, v TEXT)")
        conn.executemany("INSERT INTO t VALUES (?, ?)",
                         [(i, f"v{i}") for i in range(100)])
        conn.commit()
        conn.close()
        path = f.name
        ds = read_sql("SELECT * FROM t", lambda: sqlite3.connect(path),
                      parallelism=4, partition_column="id",
                      lower_bound=0, upper_bound=100)
        rows = ds.take_all()
        assert len(rows) == 100
        assert sorted(r["id"] for r in rows) == list(range(100))


# ---------------------------------------------------------------------------
# unknown runtime_env keys (ADVICE r5 #5)
# ---------------------------------------------------------------------------


def test_unknown_runtime_env_key_fails_submission(ray_init):
    @ray_tpu.remote(runtime_env={"pipp": ["requests"]})
    def f():
        return 1

    ref = f.remote()
    with pytest.raises(Exception, match="pipp"):
        ray_tpu.get(ref, timeout=60)


def test_registered_plugin_key_accepted():
    from ray_tpu._private.runtime_env_mgr import (
        RuntimeEnvPlugin,
        prepare_runtime_env,
        register_runtime_env_plugin,
        unregister_runtime_env_plugin,
    )

    class _P(RuntimeEnvPlugin):
        name = "my_plugin"

    register_runtime_env_plugin(_P())
    try:
        out = asyncio.run(prepare_runtime_env({"my_plugin": {"x": 1}}, None))
        assert "my_plugin" in out
    finally:
        unregister_runtime_env_plugin("my_plugin")

    with pytest.raises(ValueError, match="my_plugin"):
        asyncio.run(prepare_runtime_env({"my_plugin": {"x": 1}}, None))

"""Observability: metrics registry, task-event history, timeline, state API
(reference test strategy: python/ray/tests/test_state_api.py,
test_metrics_agent.py, `ray timeline` goldens)."""

import json
import time

import pytest

import ray_tpu
from ray_tpu.util import state
from ray_tpu.util.metrics import Counter, Gauge, Histogram, prometheus_text


@pytest.fixture(scope="module")
def ray_init():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


def test_metric_validation():
    with pytest.raises(ValueError):
        Counter("")
    c = Counter("neg_test_counter")
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        Histogram("bad_hist", boundaries=[])
    h = Histogram("tag_hist", boundaries=[1, 2], tag_keys=("a",))
    with pytest.raises(ValueError):
        h.observe(1.0, tags={"nope": "x"})


def test_metrics_flow_to_control_store(ray_init):
    @ray_tpu.remote
    def work(i):
        from ray_tpu.util.metrics import Counter, Histogram

        c = Counter("rt_test_requests", "test counter", tag_keys=("kind",))
        c.inc(1, tags={"kind": "unit"})
        h = Histogram("rt_test_latency", "test hist",
                      boundaries=[0.1, 1.0, 10.0])
        h.observe(0.05 * (i + 1))
        time.sleep(1.5)  # let the worker's telemetry loop flush
        return i

    assert ray_tpu.get([work.remote(i) for i in range(4)], timeout=120) == [
        0, 1, 2, 3
    ]
    deadline = time.time() + 15
    text = ""
    while time.time() < deadline:
        text = prometheus_text()
        if "rt_test_requests" in text and "rt_test_latency_bucket" in text:
            break
        time.sleep(0.5)
    assert 'rt_test_requests{kind="unit"}' in text
    assert "rt_test_latency_sum" in text
    # counters aggregate across the reporting workers
    for line in text.splitlines():
        if line.startswith("rt_test_requests{"):
            assert float(line.split()[-1]) >= 1.0


def test_task_events_and_state_api(ray_init):
    @ray_tpu.remote
    def traced_task():
        return "t"

    @ray_tpu.remote
    class TracedActor:
        def method(self):
            return "m"

    assert ray_tpu.get(traced_task.remote(), timeout=60) == "t"
    a = TracedActor.remote()
    assert ray_tpu.get(a.method.remote(), timeout=60) == "m"

    deadline = time.time() + 15
    tasks = []
    while time.time() < deadline:
        tasks = state.list_tasks()
        names = {t["name"] for t in tasks}
        if any("traced_task" in n for n in names) and "method" in names:
            break
        time.sleep(0.5)
    names = {t["name"] for t in tasks}
    assert any("traced_task" in n for n in names), names
    assert "method" in names
    summary = state.summarize_tasks()
    assert summary.get("FINISHED", 0) >= 2

    nodes = state.list_nodes()
    assert len(nodes) == 1 and nodes[0]["state"] == "ALIVE"
    actors = state.list_actors()
    assert any(x["state"] == "ALIVE" for x in actors)
    jobs = state.list_jobs()
    assert len(jobs) >= 1
    ray_tpu.kill(a)


def test_timeline_export(ray_init, tmp_path):
    @ray_tpu.remote
    def span_task():
        time.sleep(0.05)
        return 1

    ray_tpu.get([span_task.remote() for _ in range(3)], timeout=60)
    deadline = time.time() + 15
    while time.time() < deadline:
        done = sum(1 for t in state.list_tasks() if "span_task" in t["name"])
        if done >= 3:
            break
        time.sleep(0.5)
    out = str(tmp_path / "trace.json")
    state.timeline(out)
    trace = json.load(open(out))
    spans = [e for e in trace if "span_task" in e["name"]]
    assert len(spans) >= 3
    for e in spans:
        assert e["ph"] == "X" and e["dur"] > 0 and e["pid"].startswith("node:")


def test_placement_group_listing(ray_init):
    from ray_tpu.util.placement_group import (
        placement_group,
        remove_placement_group,
    )

    pg = placement_group([{"CPU": 1}], strategy="PACK")
    assert pg.ready(timeout=60)
    pgs = state.list_placement_groups()
    assert any(p["state"] == "CREATED" for p in pgs)
    remove_placement_group(pg)


def test_jax_profiler_capture(ray_init, tmp_path):
    """JAX profiler capture inside a live actor's own process, the path
    the benchmark and an operator use to trace a process that holds a
    chip, writes an XPlane trace (reference: jax_profile_manager.py
    capture + util/tpu.py profiler)."""
    from ray_tpu.tpu.profiler import capture_in_actor

    @ray_tpu.remote
    class Holder:
        def ping(self):
            return 1

    actor = Holder.remote()
    assert ray_tpu.get(actor.ping.remote(), timeout=60) == 1
    try:
        files = capture_in_actor(actor, str(tmp_path / "prof"),
                                 duration_s=0.5)
    finally:
        ray_tpu.kill(actor)
    assert files, "no trace files produced"
    assert any(f.endswith(".xplane.pb") or "trace" in f for f in files), files


def test_cluster_event_stream_and_export(ray_init, tmp_path):
    """Structured event export pipeline (VERDICT missing #9): lifecycle
    events collected cluster-wide, queryable, and exportable as JSONL."""
    from ray_tpu.util.state import export_cluster_events, list_cluster_events

    @ray_tpu.remote
    class Marker:
        def ping(self):
            return 1

    a = Marker.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == 1

    events = list_cluster_events()
    assert events, "no cluster events recorded"
    sources = {e["source"] for e in events}
    assert "node" in sources  # head registration
    assert any(e["type"] == "REGISTERED" for e in events)
    assert any(e["source"] == "actor" and e["type"] == "ALIVE"
               for e in events)
    # filters
    only_nodes = list_cluster_events(source="node")
    assert only_nodes and all(e["source"] == "node" for e in only_nodes)
    # seq strictly increasing
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs)
    # custom events via report_event
    from ray_tpu._private.core_worker import get_core_worker

    cw = get_core_worker()
    cw.run_sync(cw.control.call("report_event", {
        "source": "test", "type": "CUSTOM", "message": "hello",
        "meta": {"k": 1}}), 10)
    got = list_cluster_events(source="test")
    assert got and got[-1]["message"] == "hello"
    # JSONL export through the storage plane
    dest = str(tmp_path / "events.jsonl")
    n = export_cluster_events(dest)
    assert n >= len(events)
    import json as _json

    lines = [l for l in open(dest).read().splitlines() if l]
    assert len(lines) == n
    assert _json.loads(lines[0])["seq"]

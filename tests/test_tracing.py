"""Distributed tracing: span propagation through task specs
(VERDICT missing #8; reference: util/tracing/tracing_helper.py:181 —
trace context injected into the TaskSpec, spans around execution)."""

import time

import pytest

import ray_tpu
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def ray_init():
    tracing.enable_tracing()  # before init: workers inherit the env
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


def test_spans_chain_across_nested_tasks(ray_init):
    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent(x):
        # nested submission from inside a task must CHAIN, not start a
        # fresh trace
        return ray_tpu.get(child.remote(x), timeout=60) + 10

    assert ray_tpu.get(parent.remote(1), timeout=120) == 12

    deadline = time.time() + 60
    spans = []
    while time.time() < deadline:
        spans = [s for s in tracing.list_spans()
                 if s.get("event") == "SPAN"
                 and s["name"].split(".")[-1] in ("parent", "child")
                 or (s.get("event") == "SPAN"
                     and ("parent" in s["name"] or "child" in s["name"]))]
        if len(spans) >= 2:
            break
        time.sleep(0.5)
    assert len(spans) >= 2, spans
    par = next(s for s in spans if "parent" in s["name"])
    chi = next(s for s in spans if "child" in s["name"])
    assert par["trace_id"] == chi["trace_id"], "nested call split the trace"
    assert chi["parent_span_id"] == par["span_id"], (
        "child span not parented to the caller's span")
    assert par["parent_span_id"] == ""  # driver-rooted trace
    assert par["duration_s"] >= 0


def test_actor_method_spans(ray_init):
    @ray_tpu.remote
    class Svc:
        def work(self, x):
            return x * 2

    a = Svc.remote()
    assert ray_tpu.get(a.work.remote(4), timeout=120) == 8
    deadline = time.time() + 60
    got = []
    while time.time() < deadline:
        got = [s for s in tracing.list_spans()
               if s.get("event") == "SPAN" and s["name"] == "work"]
        if got:
            break
        time.sleep(0.5)
    assert got, "actor method produced no span"
    assert got[0]["trace_id"] and got[0]["span_id"]


def test_tracing_off_adds_no_context():
    from ray_tpu._private.protocol import TaskSpec
    from ray_tpu.util import tracing as tr

    old = tr._ENABLED
    import os

    env_old = os.environ.pop("RT_TRACING_ENABLED", None)
    tr._ENABLED = False
    try:
        assert tr.inject_context() is None
        spec = TaskSpec.from_wire(TaskSpec(
            task_id=__import__("ray_tpu._private.ids", fromlist=["TaskID"])
            .TaskID.nil(), job_id=__import__(
                "ray_tpu._private.ids", fromlist=["JobID"]).JobID.nil(),
        ).to_wire())
        assert spec.trace_ctx is None
    finally:
        tr._ENABLED = old
        if env_old is not None:
            os.environ["RT_TRACING_ENABLED"] = env_old


def test_actor_init_and_streaming_spans(ray_init):
    """Spans cover actor __init__ (nested submissions chain from it) and
    the full iteration of streaming tasks."""
    @ray_tpu.remote
    def leaf():
        return 1

    @ray_tpu.remote
    class Nester:
        def __init__(self):
            self.n = ray_tpu.get(leaf.remote(), timeout=60)

        def get(self):
            return self.n

    a = Nester.remote()
    assert ray_tpu.get(a.get.remote(), timeout=120) == 1

    @ray_tpu.remote(num_returns="streaming")
    def gen():
        for i in range(3):
            time.sleep(0.05)
            yield i

    assert [ray_tpu.get(r, timeout=60) for r in gen.remote()] == [0, 1, 2]

    deadline = time.time() + 60
    spans = []
    while time.time() < deadline:
        spans = tracing.list_spans()
        names = {s["name"] for s in spans}
        if (any("leaf" in n for n in names)
                and any("gen" in n for n in names)
                and any("Nester" in n for n in names)):
            break
        time.sleep(0.5)
    leaf_s = next(s for s in spans if "leaf" in s["name"])
    init_s = next(s for s in spans if "Nester" in s["name"])
    assert leaf_s["trace_id"] == init_s["trace_id"]
    assert leaf_s["parent_span_id"] == init_s["span_id"]
    gen_s = next(s for s in spans if "gen" in s["name"])
    # span covers iteration (3 x 50ms), not just generator construction
    assert gen_s["duration_s"] > 0.1, gen_s


def test_streaming_generator_body_chains(ray_init):
    """Nested submissions from INSIDE a sync streaming generator's body
    (which runs on pool threads during iteration) chain to the task span."""
    @ray_tpu.remote
    def inner(i):
        return i

    @ray_tpu.remote(num_returns="streaming")
    def streamer():
        for i in range(2):
            yield ray_tpu.get(inner.remote(i), timeout=60)

    assert [ray_tpu.get(r, timeout=60) for r in streamer.remote()] == [0, 1]
    deadline = time.time() + 60
    while time.time() < deadline:
        spans = tracing.list_spans()
        outer = [s for s in spans if "streamer" in s["name"]]
        inners = [s for s in spans if "inner" in s["name"]]
        if outer and len(inners) >= 2:
            break
        time.sleep(0.5)
    assert outer and len(inners) >= 2
    for s in inners:
        assert s["trace_id"] == outer[0]["trace_id"]
        assert s["parent_span_id"] == outer[0]["span_id"]


@pytest.mark.parametrize("parent,parent_id", [
    ({"trace_id": "t" * 32, "span_id": "s" * 16, "parent_span_id": "x"},
     "s" * 16),                                     # a span: its child
    ({"trace_id": "t" * 32, "parent_span_id": "p" * 16}, "p" * 16),  # wire ctx
])
def test_record_interval_is_a_child_with_explicit_ends(monkeypatch, parent,
                                                       parent_id):
    """The helper behind the `hop:` segments and the engine's request
    phases: start and end as stamped, never negative, the parent's trace."""
    recorded = []
    monkeypatch.setattr(tracing, "record_span",
                        lambda span, task_id=b"": recorded.append(
                            (span, task_id)))
    tracing.record_interval(parent, "hop:flight", 10.0, 12.5, task_id=b"tid")
    tracing.record_interval(parent, "hop:reply", 10.0, 9.0)
    (a, tid), (b, _) = recorded
    assert (a["trace_id"], a["parent_span_id"]) == ("t" * 32, parent_id)
    assert (a["name"], a["start"], a["end"], tid) == (
        "hop:flight", 10.0, 12.5, b"tid")
    assert (b["start"], b["end"]) == (10.0, 10.0)
    assert len(a["span_id"]) == 16 and a["span_id"] != b["span_id"]


def test_new_trace_span_ignores_the_inherited_context(ray_init):
    """A server's handlers inherit the context the server was started in:
    `new_trace` roots a request's span in a trace of its own, and spans
    opened under it chain to it."""
    with tracing.span("server-start") as outer:
        with tracing.span("ingress:x", new_trace=True) as root:
            with tracing.span("inner") as inner:
                pass
        manual = tracing.start_manual_span("ingress:y", new_trace=True)
        chained = tracing.start_manual_span("handle:pick")
    assert root["trace_id"] != outer["trace_id"]
    assert root["parent_span_id"] == ""
    assert (inner["trace_id"], inner["parent_span_id"]) == (
        root["trace_id"], root["span_id"])
    assert manual["trace_id"] not in (outer["trace_id"], root["trace_id"])
    assert manual["parent_span_id"] == ""
    assert chained["trace_id"] == outer["trace_id"]


@pytest.mark.parametrize("dropped", [0, 7])
def test_list_spans_says_how_many_events_were_dropped(monkeypatch, caplog,
                                                      dropped):
    """The control store's `dropped` count reaches the reader of a trace:
    a warning with the number when events were lost, none otherwise, and
    the spans that were kept either way."""
    from ray_tpu.util import state

    events = [{"event": "SPAN", "task_id": b"\x01", "name": "ingress:x",
               "trace_id": "t" * 32, "span_id": "s" * 16, "ts": 1.0,
               "duration_s": 0.5},
              {"event": "FINISHED", "task_id": b"\x01", "name": "f"}]
    monkeypatch.setattr(state, "_control_call", lambda method, payload: {
        "events": events, "dropped": dropped})
    with caplog.at_level("WARNING", logger=tracing.logger.name):
        spans = tracing.list_spans()
    assert [s["name"] for s in spans] == ["ingress:x"]
    warned = [r.getMessage() for r in caplog.records
              if r.name == tracing.logger.name]
    if dropped:
        assert len(warned) == 1 and "7 task events were dropped" in warned[0]
    else:
        assert warned == []

"""Engine-process tracing: the loop's phases as profiler annotations, the
three per-request spans on the tracing plane, one trace id per HTTP
request, and the profiler hook inside an actor's own process."""

import asyncio
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import GLOBAL_CONFIG
from ray_tpu.llm import _engine
from ray_tpu.llm._engine import EngineConfig, PagedEngine
from ray_tpu.models.llama import LlamaConfig, init_params
from ray_tpu.util import tracing

CFG = LlamaConfig(
    vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
    ffn_dim=128, max_seq_len=128, dtype=jnp.float32, param_dtype=jnp.float32)
SPANS = (_engine.SPAN_QUEUE, _engine.SPAN_PREFILL, _engine.SPAN_DECODE)


def small_ecfg(**kw):
    return EngineConfig(max_num_seqs=2, kv_block_size=4, num_kv_blocks=32,
                        max_model_len=64, **kw)


def small_engine(**kw):
    return PagedEngine(CFG, init_params(CFG, jax.random.PRNGKey(0)),
                       small_ecfg(**kw))


def host_events(logdir):
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb"))
    assert paths, f"no .xplane.pb under {logdir}"
    return [e for plane in ProfileData.from_file(paths[-1]).planes
            for line in plane.lines for e in line.events]


def host_event_names(logdir):
    return {e.name for e in host_events(logdir)}


def timed_events(tmp_path, eng, prompts, pause_s=0.05, outside=True):
    """The engine's events, [(name, {argument: value}, start_ns, end_ns)] by
    start, while it serves `prompts` one after the other, left empty for
    `pause_s` before each, after one request outside the capture (it
    compiles; without `outside` the engine's loop starts inside the
    capture)."""
    async def one(prompt):
        return [t async for t in eng.generate_stream(prompt, max_tokens=3)]

    async def main():
        if outside:
            await one(prompts[0][:-1] + [1])
        jax.profiler.start_trace(str(tmp_path))
        try:
            for prompt in prompts:
                await asyncio.sleep(pause_s)
                await one(prompt)
        finally:
            await asyncio.to_thread(jax.profiler.stop_trace)

    asyncio.run(main())
    return sorted(((e.name, dict(e.stats), e.start_ns,
                    e.start_ns + e.duration_ns)
                   for e in host_events(str(tmp_path))
                   if e.name.startswith("engine:")), key=lambda e: e[2])


def captured(tmp_path, eng, prompts):
    """`timed_events` without the times: [(name, {argument: value})]."""
    return [(n, args) for n, args, _, _ in timed_events(tmp_path, eng, prompts)]


# ---------------------------------------------------------------------------
# in-process: annotations, stamps, spans
# ---------------------------------------------------------------------------

WHOLE_PROMPT_ONLY = {_engine.PHASE_PREFILL, _engine.PHASE_SAMPLE_FIRST}


def test_chunked_admission_writes_its_phases_and_each_steps_chunk_width(
        tmp_path):
    """A capture around a few engine steps, read back with ProfileData: an
    engine that admits in chunks writes every phase of `_engine.PHASES` but
    the whole-prompt prefill's two and no other (a rename fails here and not
    in a chip run: the benchmark's readers find the phases by name), and
    every `engine:step` says the width of the chunk it carried: 0 or one of
    the ladder's."""
    eng = small_engine(prefix_cache=True)
    shared = [7, 8, 9, 10, 11, 12, 13, 14]   # two full blocks
    events = captured(tmp_path, eng, [
        shared + [2, 3],                      # a chunk behind cached blocks
        [20, 21, 22],                         # a whole prompt in one chunk
        list(range(30, 30 + 40))])            # three chunks
    assert {n for n, _ in events} == set(_engine.PHASES) - WHOLE_PROMPT_ONLY
    widths = [args["chunk"] for n, args in events if n == _engine.PHASE_STEP]
    ladder = _engine.chunk_ladder(eng.ecfg)
    assert set(widths) <= {0, *ladder} and {0, ladder[0], ladder[-1]} <= set(
        widths)
    # three requests of 3 tokens: 5 chunks and 6 steps without one
    assert sorted(widths) == [0] * 6 + sorted([8, 8, 16, 16, 8])


def test_whole_prompt_admission_writes_every_phase(tmp_path):
    """The family whose steps take no chunk (Ling) is admitted by whole
    prompts awaited in the loop: with the chunked path's phases above,
    `PHASES` names exactly what the two still write."""
    from ray_tpu.models import ling

    cfg = ling.LingConfig.tiny()
    eng = PagedEngine(cfg, ling.init_params(cfg, jax.random.PRNGKey(0)),
                      EngineConfig(max_num_seqs=2, kv_block_size=16,
                                   num_kv_blocks=16, max_model_len=64))
    events = captured(tmp_path, eng, [[20, 21, 22]])
    assert {n for n, _ in events} == set(_engine.PHASES)
    assert {args["chunk"] for n, args in events
            if n == _engine.PHASE_STEP} == {0}
    stats = eng.stats()
    assert stats["prefill_chunks"] == stats["steps_with_chunk"] == 0


def test_an_empty_engines_wait_is_one_idle_phase_over_the_gap(tmp_path):
    """Between two requests the engine is empty for `pause_s`: the loop's
    wait for the next one is one `engine:idle` that covers the gap, and no
    turn of the loop (no step, wait, emit, sweep or admission) overlaps an
    idle phase: while a request is in the engine there is none. (The pause
    before the first request may leave one too; a wait already under way
    when a capture starts would not, an annotation is recorded only if it
    began inside the profiler session.)"""
    pause_s = 0.4
    events = [(n, s, e) for n, _, s, e in timed_events(
        tmp_path, small_engine(), [[20, 21, 22], [23, 24, 25]], pause_s)]
    steps = [(s, e) for n, s, e in events if n == _engine.PHASE_STEP]
    # the widest gap between two steps is the pause between the two requests
    gap_lo, gap_hi = max(((a[1], b[0]) for a, b in zip(steps, steps[1:])),
                         key=lambda g: g[1] - g[0])
    idle = [e for e in events if e[0] == _engine.PHASE_IDLE]
    between = [(s, e) for _, s, e in idle if gap_lo < s and e < gap_hi]
    assert len(between) == 1 and len(idle) <= 2, idle
    lo, hi = between[0]
    assert 0.9 * pause_s <= (hi - lo) * 1e-9 <= (gap_hi - gap_lo) * 1e-9
    for _, lo, hi in idle:
        inside = [(n, s, e) for n, s, e in events
                  if n != _engine.PHASE_IDLE and s < hi and e > lo]
        assert not inside, inside


def test_chunk_counters_account_for_every_prompt_token():
    """`prefill_chunk_tokens` is the prompt tokens sent less the blocks the
    prefix cache handed out, exactly; a chunk a step that carries one."""
    eng = small_engine(prefix_cache=True)
    shared = list(range(100, 100 + 22))       # five full blocks of 4
    prompts = [shared + [1, 2, 3], shared + [4], [9] * 37, shared[:9]]

    async def main():
        for prompt in prompts:
            assert len([t async for t in eng.generate_stream(
                prompt, max_tokens=4)]) == 4

    asyncio.run(main())
    stats = eng.stats()
    hits = stats["prefix_cache"]["block_hits"]
    assert hits == 5 + 2
    assert stats["prefill_chunk_tokens"] == sum(map(len, prompts)) - 4 * hits
    assert stats["prefill_chunks"] == stats["steps_with_chunk"] == 2 + 1 + 3 + 1
    assert stats["steps_with_chunk"] <= stats["steps"] == 7 + 3 * len(prompts)
    # 25 = 16 + 9 -> 16; 3 -> 8; 37 = 16 + 16 + 5 -> 8; 1 -> 8
    assert stats["prefill_chunk_pad_tokens"] == 7 + 5 + 3 + 7


# ---------------------------------------------------------------------------
# the loop's own account of its time (stats())
# ---------------------------------------------------------------------------

ACCOUNT = ("loop_turn_s", "loop_wait_s", "loop_idle_s", "turns_unwaited",
           "turn_unwaited_s")
LADDER = _engine.chunk_ladder(small_ecfg())     # (8, 16)


def serve_in_turn(eng, prompts, pause_s=0.0, marks=None):
    """Serve `prompts` one after the other on one event loop, the engine
    left empty for `pause_s` before each; `marks`, where given, gets
    (time.monotonic(), stats()) as each request's last token arrives."""
    async def main():
        for prompt in prompts:
            await asyncio.sleep(pause_s)
            assert len([t async for t in eng.generate_stream(
                prompt, max_tokens=3)]) == 3
            if marks is not None:
                marks.append((time.monotonic(), eng.stats()))

    asyncio.run(main())


def slow_to_fetch(eng, nap_s):
    """Make every step's tokens take `nap_s` to fetch, as a device that is
    still computing them does: beside such a wait what an annotation costs
    (microseconds, as long as the tiny step's own wait on the CPU) is
    nothing, as it is on the chip."""
    step = eng._decode

    class Toks:
        def __init__(self, value):
            self.value = value

        def __array__(self, dtype=None, copy=None):
            time.sleep(nap_s)
            return np.asarray(self.value)

    def decode(*args):
        # the step before's result is among the inputs (`feed_back`)
        toks, *rest = step(*(a.value if isinstance(a, Toks) else a
                             for a in args))
        return Toks(toks), *rest

    eng._decode = decode


@pytest.fixture(scope="module")
def accounted():
    """The counters of an engine that ran a prompt of one narrow chunk and
    one of two widest chunks and a narrow one."""
    eng = small_engine()
    serve_in_turn(eng, [[20, 21, 22], list(range(30, 30 + 40))])
    return eng.stats()


@pytest.mark.parametrize("width", (0, *LADDER))
def test_a_turn_is_filed_under_the_width_of_the_step_it_fetched(
        accounted, width):
    """Every width of the step has its count and its seconds, every fetched
    step is under exactly one, and the turns' seconds are the widths'
    seconds: the wait is a part of them, the unwaited turns some of them."""
    s = accounted
    assert {k for k in s if re.fullmatch(r"(steps|turn_s)_w\d+", k)} == {
        f"{name}{w}" for name in ("steps_w", "turn_s_w") for w in (0, *LADDER)}
    assert s[f"steps_w{width}"] > 0 and s[f"turn_s_w{width}"] > 0
    # 3 tokens a request: a chunk of 8; two of 16 and one of 8; the rest
    assert [s[f"steps_w{w}"] for w in (0, *LADDER)] == [s["steps"] - 4, 2, 2]
    assert sum(s[f"steps_w{w}"] for w in (0, *LADDER)) == s["steps"]
    assert sum(s[f"turn_s_w{w}"] for w in (0, *LADDER)) == pytest.approx(
        s["loop_turn_s"], rel=1e-9)
    assert 0 < s["loop_wait_s"] <= s["loop_turn_s"]
    assert 0 <= s["turns_unwaited"] <= s["steps"]
    assert 0 <= s["turn_unwaited_s"] <= s["loop_turn_s"]


def test_turns_and_idle_waits_cover_the_loops_time():
    """From one request's last token to a later one's, with the engine
    left empty in between: the turns' seconds and the idle waits' add up to
    the interval, but for each sweep that found the engine empty."""
    eng, marks = small_engine(), []
    serve_in_turn(eng, [[20, 21, 22]] + [[23, 24, 25]] * 3, 0.1, marks)
    (t0, a), (t1, b) = marks[0], marks[-1]
    covered = sum(b[k] - a[k] for k in ("loop_turn_s", "loop_idle_s"))
    assert b["loop_idle_s"] - a["loop_idle_s"] >= 3 * 0.09
    assert covered == pytest.approx(t1 - t0, rel=0.03, abs=0.04)


def test_an_empty_engine_adds_its_wait_to_the_idle_seconds_and_no_turn():
    """An engine left empty 0.4 s: when the next request ends the wait, the
    whole of it is in `loop_idle_s`, and until a step is fetched nothing is
    in `loop_turn_s`."""
    eng = small_engine()

    async def main():
        assert len([t async for t in eng.generate_stream(
            [20, 21, 22], max_tokens=3)]) == 3
        before = eng.stats()
        await asyncio.sleep(0.4)
        gen = eng.generate_stream([23, 24, 25], max_tokens=3)
        first = asyncio.ensure_future(gen.__anext__())
        while eng.stats()["loop_idle_s"] == before["loop_idle_s"]:
            await asyncio.sleep(0)
        woken = eng.stats()
        await first
        await gen.aclose()
        return before, woken

    before, woken = asyncio.run(main())
    assert woken["loop_idle_s"] - before["loop_idle_s"] >= 0.35
    assert woken["loop_turn_s"] == before["loop_turn_s"]
    assert woken["steps"] == before["steps"]


def test_the_counters_are_the_annotations_sums_and_each_wait_names_its_width(
        tmp_path):
    """A profiler session held over a whole run (the loop starts inside it):
    `loop_wait_s` is the sum of the `engine:device_wait`s and `loop_idle_s`
    that of the `engine:idle`s, each pair on the same two edges, and every
    wait carries the chunk width of the step it fetched: the widths that
    were dispatched, each fetched once."""
    eng = small_engine()
    eng.warm_up()
    slow_to_fetch(eng, 0.005)
    events = timed_events(tmp_path, eng, [
        [20, 21, 22], list(range(30, 30 + 40)), [23, 24, 25]],
        pause_s=0.1, outside=False)
    stats = eng.stats()

    def total_s(name):
        return sum(e - s for n, _, s, e in events if n == name) * 1e-9

    assert total_s(_engine.PHASE_DEVICE_WAIT) == pytest.approx(
        stats["loop_wait_s"], rel=0.02)
    assert stats["loop_idle_s"] >= 2 * 0.09
    assert total_s(_engine.PHASE_IDLE) == pytest.approx(
        stats["loop_idle_s"], rel=0.02)
    waits = [args for n, args, _, _ in events
             if n == _engine.PHASE_DEVICE_WAIT]
    assert len(waits) == stats["steps"] and all("chunk" in a for a in waits)
    assert sorted(a["chunk"] for a in waits) == sorted(
        args["chunk"] for n, args, _, _ in events if n == _engine.PHASE_STEP)
    for w in (0, *LADDER):
        assert sum(a["chunk"] == w for a in waits) == stats[f"steps_w{w}"]


def test_tracing_off_records_no_engine_span(monkeypatch):
    """Tracing off: the request carries its stamps, `stats()` splits the
    queue wait out of the time to first token, and nothing is recorded."""
    recorded = []
    monkeypatch.setattr(tracing, "record_span",
                        lambda span, task_id=b"": recorded.append(span))
    # off whatever an earlier test file of this process switched on
    monkeypatch.setattr(tracing, "_ENABLED", False)
    monkeypatch.delenv("RT_TRACING_ENABLED", raising=False)
    assert not tracing.tracing_enabled()
    eng = small_engine()

    async def main():
        return [t async for t in eng.generate_stream([1, 2, 3], max_tokens=4)]

    assert len(asyncio.run(main())) == 4
    assert recorded == []
    stats = eng.stats()
    assert 0.0 <= stats["queue_wait_p50_s"] <= stats["ttft_p50_s"]


def test_stats_carry_the_decode_attention_and_its_live_share():
    """`stats()` names the attention the decode step was built with and
    counts, step by step, the positions it had to read beside the positions
    a dense pass over max_model_len reads: two concurrent requests."""
    eng = small_engine()

    async def one(prompt, n):
        return [t async for t in eng.generate_stream(prompt, max_tokens=n)]

    async def main():
        return await asyncio.gather(one([1, 2, 3], 6), one([4, 5], 4))

    assert [len(o) for o in asyncio.run(main())] == [6, 4]
    stats = eng.stats()
    assert stats["decode_attention"] == "xla"
    assert stats["attn_positions_dense"] == stats["steps"] * 2 * 64
    # each decoded token read its own context: 3..8 and 2..4 cached + 1
    assert stats["attn_positions_live"] == sum(range(4, 9)) + sum(range(3, 6))
    assert 0 < stats["attn_positions_live"] < stats["attn_positions_dense"]


def test_traced_request_records_the_phases_it_reached(monkeypatch):
    """Under a caller's span a finished request leaves queue, prefill and
    decode as that span's children, end to end without a gap; a request
    whose consumer walks away before admission leaves only its queue."""
    recorded = []
    monkeypatch.setattr(tracing, "record_span",
                        lambda span, task_id=b"": recorded.append(span))
    eng = small_engine()
    parent = {"trace_id": "ab" * 16, "span_id": "cd" * 8}

    async def main():
        with tracing.installed_span(parent):
            done = [t async for t in eng.generate_stream(
                [1, 2, 3], max_tokens=4)]
            # both slots busy, so the third waits; its consumer leaves
            held = [eng.generate_stream([4, 5], max_tokens=40)
                    for _ in range(2)]
            for g in held:
                await g.__anext__()
            gone = eng.generate_stream([6], max_tokens=4)
            waiter = asyncio.ensure_future(gone.__anext__())
            await asyncio.sleep(0.05)
            waiter.cancel()
            await asyncio.gather(waiter, return_exceptions=True)
            await gone.aclose()
            for g in held:
                await g.aclose()
            for _ in range(50):          # the loop's sweep drops all three
                await asyncio.sleep(0.02)
                if len(recorded) >= 3 + 1 + 2 * 3:
                    break
        return done

    assert len(asyncio.run(main())) == 4
    assert all(s["trace_id"] == parent["trace_id"]
               and s["parent_span_id"] == parent["span_id"] for s in recorded)
    first = sorted(recorded[:3], key=lambda s: s["start"])
    assert [s["name"] for s in first] == list(SPANS)
    assert first[0]["end"] == first[1]["start"]
    assert first[1]["end"] == first[2]["start"]
    assert all(s["end"] >= s["start"] for s in first)
    # the abandoned request never reached admission
    by_start = {}
    for s in recorded[3:]:
        by_start.setdefault(s["name"], []).append(s)
    assert len(by_start[_engine.SPAN_QUEUE]) == 3
    assert len(by_start[_engine.SPAN_PREFILL]) == 2
    assert len(by_start[_engine.SPAN_DECODE]) == 2


# ---------------------------------------------------------------------------
# cluster: one trace per HTTP request, the profiler hook inside an actor
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_cluster():
    info = ray_tpu.init(num_cpus=8, system_config={"tracing_enabled": True})
    yield info
    ray_tpu.shutdown()


@pytest.fixture()
def traced(traced_cluster):
    # the conftest config reset runs after every test; the cluster's
    # workers inherited the flag at spawn
    GLOBAL_CONFIG.apply_system_config({"tracing_enabled": True})
    yield


@pytest.fixture(scope="module")
def dp_app(traced_cluster):
    """One tiny data-parallel engine behind a route, for the tests below:
    (handle, base URL of the HTTP proxy)."""
    from ray_tpu import serve
    from ray_tpu.llm import LLMConfig
    from ray_tpu.llm.serving_patterns import build_dp_app

    config = LLMConfig(model="tiny", max_new_tokens=4, model_overrides=dict(
        dtype=jnp.float32, param_dtype=jnp.float32))
    handle = build_dp_app(
        config, dp_size=1, deployment_name="traced_dp",
        engine_config=dict(max_num_seqs=2, kv_block_size=8,
                           num_kv_blocks=32, max_model_len=64))
    yield handle, serve.start(http_port=0)
    serve.delete("traced_dp")


def test_dp_answer_carries_first_token_and_total_time(traced, dp_app):
    """A non-streaming answer says when its first token came and when its
    last, on the replica's clock, beside the keys it had."""
    handle, _ = dp_app
    t0 = time.monotonic()
    out = handle.remote({"prompt": "time me", "max_tokens": 4}).result(
        timeout=300)
    elapsed = time.monotonic() - t0
    usage = out["usage"]
    assert set(usage) == {"completion_tokens", "dp_rank", "ttft_s", "total_s"}
    assert usage["completion_tokens"] == len(
        out["choices"][0]["token_ids"]) >= 1
    assert usage["dp_rank"] == 0
    assert 0.0 < usage["ttft_s"] <= usage["total_s"] <= elapsed


def test_each_http_request_is_one_trace_down_to_the_engine(traced, dp_app):
    import httpx

    _, base = dp_app
    for prompt in ("first request", "second"):
        r = httpx.post(f"{base}/traced_dp", json={"prompt": prompt},
                       timeout=300)
        assert r.status_code == 200, r.text
        assert r.json()["result"]["usage"]["completion_tokens"] >= 1

    def request_traces():
        by_trace = {}
        for s in tracing.list_spans(limit=4000):
            by_trace.setdefault(s["trace_id"], []).append(s)
        return {t: ss for t, ss in by_trace.items()
                if any(s["name"].startswith("ingress:") for s in ss)}

    def whole(ss):
        """The engine's three spans are in and so is every span a parent
        link names: each process flushes its own spans in its own time, so
        the engine's can land before the replica's that they hang under."""
        ids = {s["span_id"] for s in ss}
        return ({s["name"] for s in ss} >= set(SPANS) and all(
            s["parent_span_id"] in ids for s in ss if s["parent_span_id"]))

    deadline = time.time() + 60
    while time.time() < deadline:
        traces = request_traces()
        if len(traces) == 2 and all(map(whole, traces.values())):
            break
        time.sleep(0.5)
    assert len(traces) == 2, {t: sorted(s["name"] for s in ss)
                              for t, ss in traces.items()}
    for ss in traces.values():
        by_id = {s["span_id"]: s for s in ss}
        names = [s["name"] for s in ss]
        assert sum(n.startswith("ingress:") for n in names) == 1, names
        engine = {s["name"]: s for s in ss if s["name"] in SPANS}
        assert set(engine) == set(SPANS), names
        # the three hang under one completions_stream execution span...
        parents = {s["parent_span_id"] for s in engine.values()}
        assert len(parents) == 1
        up = by_id[parents.pop()]
        assert "completions_stream" in up["name"]
        # ...whose parent links lead to this trace's ingress span
        chain = [up["name"]]
        while not up["name"].startswith("ingress:"):
            up = by_id[up["parent_span_id"]]
            chain.append(up["name"])
        assert up["parent_span_id"] == "", chain
        assert any(n.startswith("handle:pick") for n in names), names
        assert any(n.startswith("replica:admit") for n in names), names
        q, p, d = (engine[n] for n in SPANS)
        assert q["ts"] <= p["ts"] <= d["ts"]
        assert q["ts"] >= up["ts"] - 0.05   # inside the ingress span


def test_capture_in_actor_traces_the_actors_own_process(traced_cluster,
                                                        tmp_path):
    """The capture runs where the actor's work runs: its annotation is in
    the trace, and its event loop keeps serving while it is traced."""
    from ray_tpu.tpu.profiler import capture_in_actor

    @ray_tpu.remote
    class Spinner:
        async def spin(self, seconds):
            import asyncio as aio

            import jax as j

            end, n = time.monotonic() + seconds, 0
            while time.monotonic() < end:
                with j.profiler.TraceAnnotation("spinner:turn"):
                    j.numpy.ones((8, 8)).sum().block_until_ready()
                n += 1
                await aio.sleep(0.005)
            return n

    actor = Spinner.remote()
    ray_tpu.get(actor.spin.remote(0.05), timeout=120)     # warm
    turns = actor.spin.remote(2.0)
    files = capture_in_actor(actor, str(tmp_path / "prof"), duration_s=0.5)
    assert any(f.endswith(".xplane.pb") for f in files), files
    assert ray_tpu.get(turns, timeout=120) > 10
    assert "spinner:turn" in host_event_names(str(tmp_path / "prof"))
    ray_tpu.kill(actor)

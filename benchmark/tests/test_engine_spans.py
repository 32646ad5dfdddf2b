"""The readers of the engine's own spans and of the device's programs
(lib/host_spans.py and the five per-layer metrics on it): on a trace written
by hand, on a small trace recorded on the chip, on a program that writes
none of it, and end to end on the CPU through run.py. Every serve cell lists
the five (PR 26), the cells judged on request time as `open_<name>`."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import layer_metrics
from benchmark.lib import host_spans

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAMES = ("engine_queue_wait_p50_ms", "engine_prefill_stall_share",
         "engine_host_ms_per_step", "decode_device_ms_per_step",
         "prefill_device_share")
MS = 1e6   # nanoseconds


def read(name, art):
    return layer_metrics.load(name).read(art)


def art_of(trace, spans=(), busy_s=None, window_s=None):
    """Artefacts as run.py holds them after a traced run."""
    art = {"host_spans": host_spans.reduce(trace, window_s),
           "spans": list(spans)}
    if busy_s is not None:
        art["trace"] = {"busy_s": busy_s, "window_s": window_s}
    return art


# --------------------------------------------------------------------------
# a trace written by hand
# --------------------------------------------------------------------------


def turn(t0, admit_ms=0.0):
    """One turn of the engine's loop from `t0` (ms): sweep 1, an admission
    of `admit_ms` (prefix match 1, the prefill awaited, first sample 1),
    the step (upload 2, dispatch 1, device wait 20), emit 1, and 1 ms of
    nothing (the event loop's other tasks) before the next sweep. Returns
    (host events, device programs, the next turn's start)."""
    ev, mod, t = [("engine:sweep", t0 * MS, 1 * MS)], [], t0 + 1
    if admit_ms:
        ev += [("engine:admit", t * MS, admit_ms * MS),
               ("engine:prefix_match", t * MS, 1 * MS),
               ("engine:prefill", (t + 1) * MS, 1 * MS),
               ("engine:sample_first", (t + 2) * MS, (admit_ms - 2) * MS)]
        mod.append(("jit_paged_prefill(11)", (t + 1.5) * MS, (admit_ms - 2) * MS))
        t += admit_ms
    ev += [("engine:step", t * MS, 23 * MS),
           ("engine:upload", t * MS, 2 * MS),
           ("engine:dispatch", (t + 2) * MS, 1 * MS),
           ("engine:device_wait", (t + 3) * MS, 20 * MS),
           ("engine:emit", (t + 23) * MS, 1 * MS)]
    # the program starts inside the dispatch and ends as the wait does
    mod.append(("jit_paged_decode_step(7)", (t + 2.5) * MS, 20.5 * MS))
    return ev, mod, t + 25


def hand_written():
    """Four turns from 100 ms; the second admits a prompt for 10 ms. The
    fifth sweep at 214 ms closes the last whole turn: the loop's wall is
    114 ms = 4 x 26 + 10. The device executes from 103.5 ms, so the window
    opens there, inside the first turn, and whole turns run from the second
    sweep (126 ms) to the fifth (214 ms): 88 ms = 3 x 26 + 10, with 3 device
    waits of 20 ms and one admission of 10 ms inside, so the host's own
    time is 88 - 60 - 10 = 18 ms = 6 ms a step."""
    host, modules, t = [], [], 100.0
    for admit_ms in (0.0, 10.0, 0.0, 0.0):
        ev, mod, t = turn(t, admit_ms)
        host += ev
        modules += mod
    ev, mod, _ = turn(t)            # the fifth turn, cut by the window's end
    host += ev[:3]
    modules += [(mod[0][0], mod[0][1], 5 * MS)]
    ops = [(f"%fusion.{i} = bf16[8,128]{{1,0}} fusion(bf16[8,128]{{1,0}} %p)",
            s, d) for i, (_, s, d) in enumerate(modules)]
    return {
        "/device:TPU:0": {"XLA Modules": modules, "XLA Ops": ops},
        "/host:CPU": {
            "python": [e for e in host if e[0] in (
                "engine:sweep", "engine:emit")] + [("$sys setprofile", 0.0, MS)],
            # the worker threads' lines share one name, as they do on the chip
            "asyncio_0": [e for e in host if e[0] not in (
                "engine:sweep", "engine:emit")],
        },
    }


def test_hand_written_loop():
    r = host_spans.reduce(hand_written())
    assert r["window"] == (103.5 * MS, 222.5 * MS)
    assert r["loop_wall_s"] == pytest.approx(0.088)
    assert r["loop"][0][:2] == ("engine:sweep", 126 * MS)
    assert host_spans.total_s(r["loop"], "engine:device_wait") == (
        pytest.approx(0.060), 3)
    assert host_spans.total_s(r["loop"], "engine:admit") == (
        pytest.approx(0.010), 1)
    busy_s = (4 * 20.5 + 8 + 5) * 1e-3
    art = art_of(hand_written(), busy_s=busy_s, spans=[
        {"name": "engine:queue", "duration_s": d} for d in (0.004, 0.030, 0.010)
    ] + [{"name": "engine:prefill", "duration_s": 9.0},
         {"name": "engine:queue", "duration_s": None}])
    assert read("engine_queue_wait_p50_ms", art) == pytest.approx(10.0)
    assert art["engine_queue_spans"] == 3
    assert read("engine_prefill_stall_share", art) == pytest.approx(100 * 10 / 88)
    assert read("engine_host_ms_per_step", art) == pytest.approx(6.0)
    # four whole executions; the fifth, cut by the window, does not count
    assert read("decode_device_ms_per_step", art) == pytest.approx(20.5)
    assert read("prefill_device_share", art) == pytest.approx(
        100 * 0.008 / busy_s)
    # the accounting identity: device + host + stall share x wall = wall,
    # up to the part of the program that runs under the dispatch (0.5 ms)
    wall = 88 / 3
    total = 20.5 + 6.0 + (10 / 88) * wall
    assert total == pytest.approx(wall + 0.5)


def test_window_leaves_out_the_head():
    """`window_s` is `xplane.reduce`'s window (it drops the trace's head):
    the last 70 ms here, so whole turns run from the sweep at 162 ms."""
    r = host_spans.reduce(hand_written(), window_s=0.070)
    assert r["window"] == (152.5 * MS, 222.5 * MS)
    assert r["loop_wall_s"] == pytest.approx(0.052)
    assert host_spans.total_s(r["loop"], "engine:admit") == (0.0, 0)
    art = {"host_spans": r, "trace": {"busy_s": 0.060, "window_s": 0.070}}
    assert read("engine_prefill_stall_share", art) == 0.0
    assert read("prefill_device_share", art) == 0.0
    # the execution that straddles 152.5 ms is left out of the mean
    assert read("decode_device_ms_per_step", art) == pytest.approx(20.5)


# --------------------------------------------------------------------------
# a trace recorded on the chip
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_engine_trace.json")) as f:
        raw = json.load(f)
    trace = {p: {line: [tuple(e) for e in ev] for line, ev in lines.items()}
             for p, lines in raw["trace"].items()}
    return trace, raw["spans"]


def test_recorded_v5e_engine_trace(recorded):
    """1.25 s of docqa on a v5e (PR 24, seed 2401000012): the device's first
    operation is the first suffix prefill's, at 11.1 ms, after the first
    sweep (2.0 ms), so whole turns run from the second sweep (298.4 ms) to
    the sixth (1125.3 ms): four turns, three of which admit a prompt
    (suffix, prefill, suffix). Summed from the event list by the script
    that cut the fixture, not through lib/host_spans.py: wall 826,966,118
    ns; four device waits 568,007,892 ns; three admissions 237,787,767 ns;
    six executions of the decode step, the five whole ones 703,649,245 ns
    together; two prefills and two suffix prefills 335,571,937 ns; the 31
    program executions that overlap the window 1,179,972,665 ns (taken for
    `busy_s`: the fixture keeps two operations a program, which keep the
    window and not the busy time)."""
    trace, spans = recorded
    r = host_spans.reduce(trace)
    assert r["window"] == (11140621.0, 1265437850.0)
    assert r["loop_wall_s"] == pytest.approx(0.826966118)
    assert host_spans.total_s(r["loop"], "engine:device_wait") == (
        pytest.approx(0.568007892), 4)
    assert host_spans.total_s(r["loop"], "engine:admit") == (
        pytest.approx(0.237787767), 3)
    assert {n for n, _, _ in r["loop"]} == {
        "engine:sweep", "engine:admit", "engine:prefix_match",
        "engine:prefill", "engine:suffix_prefill", "engine:sample_first",
        "engine:step", "engine:upload", "engine:dispatch",
        "engine:device_wait", "engine:emit"}
    art = {"host_spans": r, "spans": spans,
           "trace": {"busy_s": 1.179972665, "window_s": None}}
    stall = read("engine_prefill_stall_share", art)
    host = read("engine_host_ms_per_step", art)
    device = read("decode_device_ms_per_step", art)
    assert stall == pytest.approx(100 * 237787767 / 826966118)      # 28.75%
    assert host == pytest.approx((826966118 - 568007892 - 237787767) / 4e6)
    assert device == pytest.approx(703649245 / 5e6)                 # 140.73 ms
    assert read("prefill_device_share", art) == pytest.approx(
        100 * 335571937 / 1179972665)
    # three requests' spans: the queue waits 135.08, 135.54 and 136.38 ms
    assert read("engine_queue_wait_p50_ms", art) == pytest.approx(135.5379, abs=1e-3)
    assert art["engine_queue_spans"] == 3
    # the accounting identity, per decode step: what the device, the host
    # and admissions take is the loop's wall time, within 5% (here 0.6%:
    # a device wait outlasts its program by the copy back and the wake-up)
    wall = 826.966118 / 4
    assert device + host + stall / 100 * wall == pytest.approx(wall, rel=0.05)
    # one trace id a request, and the older reader's walk up the parent
    # links still pairs each ingress span with its execution span
    assert len({s["trace_id"] for s in spans}) == 3
    assert read("serve_plane_p50_ms", art) == pytest.approx(3.036, abs=1e-3)
    assert art["serve_plane_pairs"] == 3


@pytest.mark.parametrize("prefix", ["", "open_"])
def test_a_program_without_spans_or_names_reads_as_nothing(prefix):
    """The parent of the PR that added them: `jit_step` programs, Python
    frames on the host, no `engine:` span. Every reader returns None and
    none raises; so does a run that was not traced."""
    trace = hand_written()
    trace["/device:TPU:0"]["XLA Modules"] = [
        ("jit_step(7)", s, d) for _, s, d in trace["/device:TPU:0"]["XLA Modules"]]
    trace["/host:CPU"] = {"python": [("$_engine.py:711 run_step", 0.0, 9 * MS)]}
    parent = art_of(trace, busy_s=0.1, spans=[
        {"name": "ingress:bench", "duration_s": 1.0}])
    for art in (parent, {}, {"trace_call": None, "spans": None}):
        for name in NAMES:
            assert read(prefix + name, art) is None, name


def test_each_reader_has_its_open_twin():
    for name in NAMES:
        closed, opened = layer_metrics.load(name), layer_metrics.load("open_" + name)
        assert (opened.UNIT, opened.LAYER, opened.SOURCE) == (
            closed.UNIT, closed.LAYER, closed.SOURCE)
        assert (closed.MOVES, opened.MOVES) == ("out_tokens_per_s", "req_p50_s")
        assert opened.read is closed.read


# --------------------------------------------------------------------------
# end to end on the CPU
# --------------------------------------------------------------------------


@pytest.mark.parametrize("cell,prefix", [("tiny-docqa-closed", ""),
                                         ("tiny-chat-open", "open_")])
def test_tiny_cell_reads_the_engine_metrics_on_the_cpu(cell, prefix):
    """run.py on a tiny cell: the run stays `correct`, and the three that
    need no device (the spans, and the loop's phases from the CPU trace) are
    read; a CPU run reports them to stderr only."""
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        assert set(prefix + n for n in NAMES) <= set(json.load(f)["per_layer"])
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "7", "--seconds", "5", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["metrics"] == {}
    line = next(ln for ln in proc.stderr.splitlines()
                if "rehearsal metrics (not reported): " in ln)
    read_here = json.loads(line.split("(not reported): ", 1)[1])
    for name in NAMES[:3]:
        assert read_here[prefix + name]["value"] >= 0.0, name
    assert read_here[prefix + "engine_prefill_stall_share"]["value"] < 100.0
    for name in NAMES[3:]:          # no device plane on the CPU
        assert prefix + name not in read_here

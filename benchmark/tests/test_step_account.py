"""PR 62's six readers of the engine loop's account (`PagedEngine.stats()`:
turn seconds by chunk width, unwaited turns, stalls) on counters written by
hand, on a parent-shaped run (no such key) and in the CPU rehearsal through
tests/listed_run_account.py, which lists them and passes their counters."""

import json
import os
import re

import pytest

from benchmark import layer_metrics
from benchmark.tests import listed_run_account as account
from benchmark.tests.test_rehearsal import BENCH, RESULT_KEYS, run_cell

TWINS = {"open_decode_turn_ms": "decode_turn_ms",
         "open_engine_unwaited_turn_share": "engine_unwaited_turn_share"}
# what an accepted runner's `sum_stats` passes today
PARENT_KEYS = {"steps": 10, "tokens_out": 90, "steps_with_chunk": 4}


def art(open_, close, **more):
    return {"stats_open": {**PARENT_KEYS, **open_},
            "stats_close": {**PARENT_KEYS, **close},
            "window_s": 10.0, "device": {"count": 1}, **more}


def hand_written():
    """60 decode turns of 20 ms, 30 chunk turns of 50 ms at 256 and 10 of
    30 ms at 128 in a window of 10 s; 12 of the turns unwaited, 0.3 s; one
    stall of 1.5 s. The counters stood at other numbers when it opened."""
    open_ = {"steps_w0": 7, "turn_s_w0": 1.0, "steps_w128": 2,
             "turn_s_w128": 0.5, "steps_w256": 1, "turn_s_w256": 0.25,
             "loop_turn_s": 1.75, "loop_wait_s": 1.5, "loop_idle_s": 3.0,
             "turns_unwaited": 3, "turn_unwaited_s": 0.125,
             "loop_stalls": 1, "loop_stall_s": 2.0, "loop_stall_admit_s": 1.0}
    grew = {"steps_w0": 60, "turn_s_w0": 1.2, "steps_w128": 10,
            "turn_s_w128": 0.3, "steps_w256": 30, "turn_s_w256": 1.5,
            "loop_turn_s": 3.0, "loop_wait_s": 2.5, "loop_idle_s": 0.0,
            "turns_unwaited": 12, "turn_unwaited_s": 0.3,
            "loop_stalls": 1, "loop_stall_s": 1.5, "loop_stall_admit_s": 0.5}
    return art(open_, {k: open_[k] + v for k, v in grew.items()})


@pytest.mark.parametrize("name,value", [
    ("decode_turn_ms", 20.0), ("chunk_turn_ms", 45.0),
    ("engine_unwaited_turn_share", 10.0), ("engine_loop_stall_share", 15.0),
    ("open_decode_turn_ms", 20.0), ("open_engine_unwaited_turn_share", 10.0)])
def test_a_reader_reads_the_change_of_its_counters_over_the_window(
        name, value):
    assert layer_metrics.load(name).read(hand_written()) == pytest.approx(value)


@pytest.mark.parametrize("name", account.CLOSED + account.OPEN)
def test_a_reader_reads_nothing_where_the_runner_passes_no_such_key(name):
    """A program without the account, a runner that drops its keys and a run
    that read no counters: None, never a number from what is there
    (`steps_with_chunk` begins as `steps_w<w>` does)."""
    reader = layer_metrics.load(name)
    assert reader.read(art({}, {})) is None
    assert reader.read({}) is None
    assert reader.read({"stats_open": None, "stats_close": None}) is None


def test_the_chunk_turn_reads_nothing_without_a_chunk_step():
    """An engine whose prompts run whole has `w0` alone; a window of one
    with a ladder may hold no chunk step: no mean of no steps."""
    whole = {"steps_w0": 5, "turn_s_w0": 0.1}
    grown = {"steps_w0": 9, "turn_s_w0": 0.2}
    reader = layer_metrics.load("chunk_turn_ms")
    assert reader.read(art(whole, grown)) is None
    idle = {**whole, "steps_w64": 3, "turn_s_w64": 0.5}
    assert reader.read(art(idle, {**grown, "steps_w64": 3,
                                  "turn_s_w64": 0.5})) is None
    assert layer_metrics.load("decode_turn_ms").read(
        art(whole, grown)) == pytest.approx(25.0)


def test_the_stall_share_is_of_the_interval_the_counters_cover_and_every_engine():
    a = hand_written()
    reader = layer_metrics.load("engine_loop_stall_share")
    assert reader.read({**a, "count_covers_s": 12.0}) == pytest.approx(12.5)
    assert reader.read({**a, "device": {"count": 4}}) == pytest.approx(3.75)


def test_the_readers_state_what_benchmark_json_will_list():
    """UNIT, LAYER, SOURCE and MOVES of each reader: a layer BENCHMARK.json
    already names, a name it does not list yet, a twin its reader's own but
    for what it moves."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {m["layer"] for m in bench["per_layer"]}
    names = {m["name"] for m in bench["per_layer"]}
    for name in account.CLOSED + account.OPEN:
        mod = layer_metrics.load(name)
        assert mod.LAYER in layers and name not in names and mod.__doc__
        assert mod.LAYER == ("jitted steps" if name.endswith("_turn_ms")
                             else "engine scheduler")
        assert mod.SOURCE == "program_counter"
        assert mod.UNIT == ("ms" if name.endswith("_ms") else "%")
        assert mod.MOVES == ("req_p50_s" if name in account.OPEN
                             else "out_tokens_per_s")
    for twin, name in TWINS.items():
        a, b = layer_metrics.load(twin), layer_metrics.load(name)
        assert a.read is b.read
        assert (a.UNIT, a.LAYER, a.SOURCE) == (b.UNIT, b.LAYER, b.SOURCE)


def test_the_wrapped_sum_passes_the_account_and_nothing_a_program_lacks():
    def sum_stats(per_rank):
        return {"steps": sum(s["steps"] for s in per_rank)}

    summed = account.passing_the_account(sum_stats)
    ranks = [{"steps": 3, "steps_with_chunk": 1, "steps_w0": 2, "steps_w64": 1,
              "turn_s_w0": 0.5, "turn_s_w64": 0.25, "loop_turn_s": 0.75,
              "loop_wait_s": 0.5, "loop_idle_s": 1.0, "turns_unwaited": 1,
              "turn_unwaited_s": 0.25, "loop_stalls": 0, "loop_stall_s": 0.0,
              "loop_stall_admit_s": 0.0, "loop_stall_last_at": 7.0,
              "free_blocks": 9}] * 2
    out = summed(ranks)
    assert out.pop("loop_stall_last_at") == 7.0
    assert out == {k: 2 * v for k, v in ranks[0].items()
                   if k not in ("steps_with_chunk", "free_blocks",
                                "loop_stall_last_at")}
    assert summed([{"steps": 3, "steps_with_chunk": 1}]) == {"steps": 3}


def test_a_cell_gets_the_readers_that_move_what_it_is_judged_on():
    closed = {"end_to_end": ["out_tokens_per_s", "setup_s"],
              "per_layer": ["hbm_peak_gb", "decode_turn_ms"]}
    assert account.listed(closed) == ["hbm_peak_gb", "decode_turn_ms"] + [
        n for n in account.CLOSED if n != "decode_turn_ms"]
    opened = {"end_to_end": ["req_p50_s", "req_p90_s", "setup_s"],
              "per_layer": ["open_hbm_peak_gb"]}
    assert account.listed(opened) == ["open_hbm_peak_gb"] + account.OPEN
    train = {"end_to_end": ["train_tokens_per_s", "setup_s"],
             "per_layer": ["train_mfu"]}
    assert account.listed(train) == ["train_mfu"]


# --- the CPU rehearsal ---------------------------------------------------------


@pytest.mark.parametrize("cell,names", [
    ("tiny-docqa-closed", account.CLOSED), ("tiny-chat-open", account.OPEN)])
def test_the_rehearsal_cells_stay_correct_and_the_account_adds_up(cell, names):
    """Both tiny serve cells through listed_run_account.py: `correct`, every
    listed reader read, and the loop's account is the window's: the turns'
    and the idle waits' seconds are the interval between the two readings to
    2%, and in the closed cell the two turn times weighted by their steps,
    with the idle seconds a step, are `engine_wall_ms_per_step`."""
    proc = run_cell(cell, 1,
                    script=os.path.join("tests", "listed_run_account.py"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}

    def logged(what):
        return json.loads(next(
            ln for ln in proc.stderr.splitlines() if what in ln).split(
                what, 1)[1])

    rehearsed = logged("rehearsal metrics (not reported): ")
    assert [n for n in names if n in rehearsed] == names
    summary = logged("summary: ")
    a, b = summary["stats_open"], summary["stats_close"]
    d = {k: b[k] - a[k] for k in b if account.SUMMED.fullmatch(k)}
    widths = [k for k in d if re.fullmatch(r"steps_w\d+", k)]
    assert len(widths) == 3 and sum(d[k] for k in widths) == (
        b["steps"] - a["steps"])
    assert d["loop_turn_s"] + d["loop_idle_s"] == pytest.approx(
        summary["count_covers_s"], rel=0.02)
    assert d["loop_wait_s"] <= d["loop_turn_s"] and d["loop_stalls"] == 0
    if cell == "tiny-docqa-closed":
        # the two turn times weighted by their steps, and the engine's few
        # empty moments between sessions, are the wall of a step
        n_chunk = sum(d[k] for k in widths if k != "steps_w0")
        steps = d["steps_w0"] + n_chunk
        weighted = (rehearsed["decode_turn_ms"]["value"] * d["steps_w0"]
                    + rehearsed["chunk_turn_ms"]["value"] * n_chunk) / steps
        assert weighted + 1e3 * d["loop_idle_s"] / steps == pytest.approx(
            rehearsed["engine_wall_ms_per_step"]["value"], rel=0.02)

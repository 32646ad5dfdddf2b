"""lib/xmeta.py: the standard-library decoder of a trace's event metadata
held against `xplane_pb2` (where it imports) and against `ProfileData`, on a
small trace cut from PR 43's traced chip run of the train cell
(data/v5e_train_trace.xplane.pb: chip 0 and chip 1, the third traced step
from the loss's forward loop to the end of its backward loop, one turn of
the backward layer scan and the step's tail with the optimizer); the table by
scope and pass on traces written by hand; the nine readers of PR 43 on the
recorded trace, on a parent-shaped one (no scope) and in the CPU rehearsal."""

import copy
import json
import os
import shutil

import pytest

from benchmark import layer_metrics
from benchmark.lib import host_spans, xmeta, xplane
from benchmark.tests.listed_run import OPEN, PENDING, TRAIN
from benchmark.tests.test_rehearsal import BENCH, RESULT_KEYS, run_cell

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_train_trace.xplane.pb")
SCOPE_SHARES = ["train_layers_fwd_share", "train_layers_bwd_share",
                "train_loss_share", "train_optimizer_share"]
NEED_NO_SCOPE = ["train_remat_share", "train_matmul_share",
                 "train_matmul_mfu", "train_step_device_ms"]
LAYER = "jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint"


# --- the decoder ----------------------------------------------------------------


def pb2():
    return pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2",
        reason="the decoder is held against xplane_pb2 where it imports")


def test_the_field_numbers_are_xplane_protos():
    """Every number xmeta.py's docstring states, against the descriptors."""
    proto = pb2()
    want = {
        "XSpace": {"planes": 1},
        "XPlane": {"name": 2, "lines": 3, "event_metadata": 4,
                   "stat_metadata": 5},
        "XLine": {"name": 2, "timestamp_ns": 3, "events": 4},
        "XEvent": {"metadata_id": 1, "offset_ps": 2, "duration_ps": 3,
                   "stats": 4},
        "XEventMetadata": {"id": 1, "name": 2, "display_name": 4, "stats": 5},
        "XStat": {"metadata_id": 1, "double_value": 2, "uint64_value": 3,
                  "int64_value": 4, "str_value": 5, "bytes_value": 6,
                  "ref_value": 7},
        "XStatMetadata": {"id": 1, "name": 2},
    }
    for message, fields in want.items():
        by_name = getattr(proto, message).DESCRIPTOR.fields_by_name
        assert {f: by_name[f].number for f in fields} == fields, message
    entry = proto.XPlane.DESCRIPTOR.fields_by_name["event_metadata"].message_type
    assert {f.name: f.number for f in entry.fields} == {"key": 1, "value": 2}


def test_the_decoder_reads_what_xplane_pb2_reads():
    proto = pb2()
    space = proto.XSpace()
    with open(RECORDED, "rb") as f:
        space.ParseFromString(f.read())
    got = xmeta.load(RECORDED)
    device = [p for p in space.planes if xplane.DEVICE_PLANE.match(p.name)]
    assert sorted(got) == sorted(p.name for p in device) and len(got) == 2
    events = 0
    for plane in device:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        for line in plane.lines:
            if line.name not in xmeta.LINES:
                continue
            ops = got[plane.name][line.name]
            assert len(ops) == len(line.events)
            for (start, dur, meta), ev in zip(ops, line.events):
                events += 1
                assert start == line.timestamp_ns + ev.offset_ps / 1e3
                assert dur == ev.duration_ps / 1e3
                md = plane.event_metadata[ev.metadata_id]
                want = dict.fromkeys(xmeta.META_KEYS)
                want["name"] = md.name
                for s in md.stats:
                    key = stat_names[s.metadata_id]
                    if key in want and key != "name":
                        kind = s.WhichOneof("value")
                        want[key] = (stat_names[s.ref_value]
                                     if kind == "ref_value"
                                     else getattr(s, kind))
                assert meta == want
    assert events > 300


def test_the_decoder_keeps_profile_datas_events_in_order():
    """Names, order and times (ProfileData cuts a time to whole
    nanoseconds) of the lines `xplane.load` reads."""
    got, trace = xmeta.load(RECORDED), xplane.load(RECORDED)
    assert sorted(got) == xplane.device_planes(trace)
    for plane, lines in got.items():
        for line, ops in lines.items():
            theirs = trace[plane][line]
            assert [m["name"] for _, _, m in ops] == [n for n, _, _ in theirs]
            for (s, d, _), (_, s2, d2) in zip(ops, theirs):
                assert abs(s - s2) < 1.0 and abs(d - d2) < 1.0
    ops = [m for _, _, m in got["/device:TPU:0"][xplane.OPS_LINE]]
    assert {m["hlo_category"] for m in ops} >= {
        "convolution fusion", "loop fusion", "custom-call"}
    assert all(isinstance(m["model_flops"], int) for m in ops)
    module = got["/device:TPU:0"][host_spans.MODULES_LINE][0][2]
    assert module["name"].startswith("jit_train_step(")
    assert str(ops[0]["program_id"]) in module["name"]


def test_not_an_xplane_file_is_an_error(tmp_path):
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(b"\x0f\x01")          # wire type 7
    with pytest.raises(ValueError):
        xmeta.load(str(path))


# --- scope and pass ------------------------------------------------------------


def test_a_wrapped_scope_is_found_among_the_tokens():
    op = LAYER + "/attn/dot_general:"
    assert xmeta.tokens(op) == [
        "jit", "train_step", "transpose", "jvp", "layers", "while", "body",
        "closed_call", "checkpoint", "attn", "dot_general"]
    assert xmeta.classify({"tf_op": op}) == ("layers", "attn", "bwd")


@pytest.mark.parametrize("tf_op,want", [
    ("jit(train_step)/jvp(layers)/while/body/closed_call/mlp/dot_general:",
     ("layers", "mlp", "fwd")),
    (LAYER + "/rematted_computation/attn/flash_fwd:",
     ("layers", "attn", "remat")),
    # both forms of one einsum in one file: unwrapped forward, wrapped backward
    ("jit(train_step)/jvp(loss)/while/body/closed_call/"
     "...bsd,...dv->...bsv/dot_general:", ("loss", None, "fwd")),
    ("jit(train_step)/transpose(jvp(loss))/while/body/closed_call/"
     "transpose(jvp(...bsd,...dv->...bsv))/dot_general:",
     ("loss", None, "bwd")),
    # the primitive `transpose` in a forward pass is no transform
    ("jit(train_step)/jvp(loss)/transpose:", ("loss", None, "fwd")),
    ("jit(train_step)/optimizer/add:", ("optimizer", None, "fwd")),
    ("jit(train_step)/transpose(jvp(embed))/scatter-add:",
     ("embed", None, "bwd")),
    # attn without layers (hoisted out of the scan) is no layer's time
    ("jit(train_step)/attn/jit(tril)/iota:", (xmeta.REST, None, "fwd")),
    ("jit(fn)/convert_element_type:", (xmeta.REST, None, "fwd")),
    (None, (xmeta.NO_TF_OP, None, "fwd")), ("", (xmeta.NO_TF_OP, None, "fwd")),
])
def test_classify(tf_op, want):
    assert xmeta.classify({"tf_op": tf_op}) == want


# --- the table, on traces written by hand ---------------------------------------


def meta(tf_op, category="loop fusion", flops=0, name="%op"):
    return {"name": name, "tf_op": tf_op, "hlo_category": category,
            "model_flops": flops, "bytes_accessed": 0, "program_id": 7}


def hand_written():
    """Two chips, nanoseconds, window [0, 1000).

    chip 0: embed [0,50); a forward `while` [50,450) around a matmul
            [60,260) of 4e5 operations and a kernel [260,400): the while
            itself counts 400 - 200 - 140 = 60; loss forward [450,550);
            a backward while [550,900) around a recomputed kernel [560,660)
            and a backward matmul [660,860) of 8e5: itself 50; optimizer
            [900,1000). One step [0,1000).
    chip 1: the same but idle where chip 0's embed is, and a collective
            without a `tf_op` [0,30).
    """
    fwd, bwd = "jit(train_step)/jvp(layers)", "jit(train_step)/transpose(jvp(layers))"
    step = (0.0, 1000.0, {"name": "jit_train_step(7)"})

    def chip(first):
        return {xplane.OPS_LINE: [
            first,
            (50.0, 400.0, meta(fwd + "/while:", "while")),
            (60.0, 200.0, meta(fwd + "/while/body/mlp/dot_general:",
                               "convolution fusion", 4e5)),
            (260.0, 140.0, meta(fwd + "/while/body/attn/flash_fwd:",
                                "custom-call", 1e5)),
            (450.0, 100.0, meta("jit(train_step)/jvp(loss)/reduce_sum:")),
            (550.0, 350.0, meta(bwd + "/while:", "while")),
            (560.0, 100.0, meta(bwd + "/while/body/checkpoint/"
                                "rematted_computation/attn/flash_fwd:",
                                "custom-call", 1e5)),
            (660.0, 200.0, meta(bwd + "/while/body/checkpoint/mlp/dot_general:",
                                "convolution fusion", 8e5)),
            (900.0, 100.0, meta("jit(train_step)/optimizer/add:")),
        ], host_spans.MODULES_LINE: [step]}

    return {
        "/device:TPU:0": chip((0.0, 50.0, meta(
            "jit(train_step)/jvp(embed)/gather:"))),
        "/device:TPU:1": chip((0.0, 30.0, meta(None, "all-gather"))),
        "/host:CPU": {},
    }


def test_a_while_counts_what_its_body_does_not():
    ops = hand_written()["/device:TPU:0"][xplane.OPS_LINE]
    by_op = {m["tf_op"]: d for d, _, m in xmeta.exclusive(ops, 0.0, 1000.0)}
    assert by_op["jit(train_step)/jvp(layers)/while:"] == 60.0
    assert by_op["jit(train_step)/transpose(jvp(layers))/while:"] == 50.0
    assert sum(by_op.values()) == 1000.0
    # a window's edge cuts the while and the matmul inside it alike
    cut = xmeta.exclusive(ops, 100.0, 1000.0)
    assert sum(d for d, _, _ in cut) == 900.0
    inside = {m["tf_op"]: share for _, share, m in cut}
    assert inside["jit(train_step)/jvp(layers)/while/body/mlp/dot_general:"] == 0.8


def test_the_table_sums_to_busy_and_averages_over_the_chips():
    r = xmeta.reduce(hand_written(), 0.0, 1000.0)
    ns = host_spans.NS
    assert r["chips"] == 2 and r["scoped"]
    assert r["busy_s"] == pytest.approx(990 * ns)       # (1000 + 980) / 2
    assert r["steps"] == 1.0 and r["step_device_s"] == pytest.approx(1000 * ns)
    sec = xmeta.seconds
    assert sec(r, None, ("remat",)) == pytest.approx(100 * ns)
    assert sec(r, "layers", ("fwd",)) == pytest.approx(400 * ns)
    assert sec(r, "layers", ("bwd",)) == pytest.approx(250 * ns)
    assert sec(r, "loss", ("fwd", "bwd")) == pytest.approx(100 * ns)
    assert sec(r, "optimizer") == pytest.approx(100 * ns)
    assert sec(r, "embed") == pytest.approx(25 * ns)
    assert sec(r, xmeta.NO_TF_OP) == pytest.approx(15 * ns)
    assert sum(c["s"] for c in r["cells"].values()) == pytest.approx(r["busy_s"])
    # attn and mlp only inside layers
    assert {k[1] for k in r["cells"] if k[0] != "layers"} == {None}
    assert r["matmul_s"] == pytest.approx(400 * ns)
    assert r["matmul_flops"] == pytest.approx(12e5)
    lines = xmeta.table_lines(r)
    assert lines[0].startswith("train scopes: 1.00 steps")
    rows = [ln.split()[2:4] for ln in lines[1:]]
    assert rows == [["embed", "fwd"], ["layers", "fwd"], ["layers", "bwd"],
                    ["layers", "remat"], ["layers/attn", "fwd"],
                    ["layers/attn", "remat"], ["layers/mlp", "fwd"],
                    ["layers/mlp", "bwd"], ["loss", "fwd"],
                    ["optimizer", "fwd"], ["no", "tf_op"]]
    # a window of half a step holds half an execution and no whole one
    half = xmeta.reduce(hand_written(), 500.0, 1000.0)
    assert half["steps"] == 0.5 and half["step_device_s"] is None   # starts before it
    assert xmeta.reduce({"/host:CPU": {}}, 0.0, 1.0) is None


# --- the readers ------------------------------------------------------------------


def traced_art(tmp_path, path=RECORDED):
    """What run.py hands a reader after a traced run whose trace is `path`."""
    logdir = tmp_path / "trace"
    at = logdir / "plugins" / "profile" / "2026_10_01"
    at.mkdir(parents=True)
    shutil.copy(path, at / "vm.xplane.pb")
    return {"trace_call": {"logdir": str(logdir)},
            "trace": xplane.reduce(xplane.load(path)),
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def read_all(art):
    return {name: layer_metrics.load(name).read(art) for name in TRAIN}


def test_the_recorded_trace_reads_all_eight_and_they_add_up(tmp_path, capfd):
    art = traced_art(tmp_path)
    got = read_all(art)
    assert all(isinstance(v, float) for v in got.values()), got
    r = art["xmeta"]
    busy = art["trace"]["busy_s"]
    # the decoder's exclusive times are the busy time xplane.reduce found
    assert r["busy_s"] == pytest.approx(busy, rel=1e-4)
    rest = 100.0 * sum(xmeta.seconds(r, s, ("fwd", "bwd")) for s in (
        "embed", xmeta.REST, xmeta.NO_TF_OP)) / busy
    shares = [got[n] for n in ["train_remat_share"] + SCOPE_SHARES]
    assert sum(shares) + rest == pytest.approx(100.0, abs=0.1)
    assert all(0.0 < s < 100.0 for s in shares)
    # (0.81% in the whole run; the cut keeps the step's head and tail, where
    # the weights' casts carry none, and one turn in 24 of each scan)
    assert 100.0 * xmeta.seconds(r, xmeta.NO_TF_OP) / busy < 5.0
    assert 0.0 < got["train_matmul_share"] < 100.0
    assert 50.0 < got["train_matmul_mfu"] < 100.0
    assert 400.0 < got["train_step_device_ms"] < 500.0
    # read once, logged once
    err = capfd.readouterr().err
    assert err.count("xmeta: read ") == 1
    assert "train scopes: layers/attn" in err and "train scopes: loss" in err


def test_a_program_without_the_scopes_reads_four_of_the_eight(
        tmp_path, monkeypatch):
    """The parent of PR 43: the same trace with no scope in any `tf_op`."""
    real = xmeta.load

    def unscoped(path):
        planes = copy.deepcopy(real(path))
        for lines in planes.values():
            for _, _, m in lines[xplane.OPS_LINE]:
                if m["tf_op"]:
                    for scope in xmeta.TOP_SCOPES + xmeta.INNER_SCOPES:
                        m["tf_op"] = m["tf_op"].replace(f"({scope})", "()")
                        m["tf_op"] = m["tf_op"].replace(f"/{scope}/", "/")
        return planes

    with_scopes = read_all(traced_art(tmp_path / "a"))
    monkeypatch.setattr(xmeta, "load", unscoped)
    got = read_all(traced_art(tmp_path / "b"))
    assert [got[n] for n in SCOPE_SHARES] == [None] * 4
    assert [got[n] for n in NEED_NO_SCOPE] == pytest.approx(
        [with_scopes[n] for n in NEED_NO_SCOPE])


def test_an_untraced_run_reads_none():
    for name in TRAIN + OPEN:
        assert layer_metrics.load(name).read({}) is None


def test_idle_share_is_the_idle_phases_over_the_loops_wall(monkeypatch):
    """Two turns of the loop around an empty engine's wait: sweeps at 0, 40
    and 100 us, `engine:idle` [10, 35) us: 25 of 100."""
    us = 1000.0
    trace = {"/host:CPU": {"loop": [
        ("engine:sweep", 0.0, 1 * us), ("engine:idle", 10 * us, 25 * us),
        ("engine:sweep", 40 * us, 1 * us), ("engine:step", 45 * us, 50 * us),
        ("engine:sweep", 100 * us, 1 * us)]}}
    reader = layer_metrics.load("open_engine_idle_share")
    assert reader.read({"host_spans": host_spans.reduce(trace)}) == 25.0
    trace["/host:CPU"]["loop"].pop(1)
    assert reader.read({"host_spans": host_spans.reduce(trace)}) == 0.0


def test_the_readers_state_what_benchmark_json_will_list():
    """UNIT, LAYER, SOURCE and MOVES of each new reader, the layer a name
    BENCHMARK.json already uses, and the cells of listed_run.py real."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    layers = {m["layer"] for m in bench["per_layer"]}
    listed = {m["name"] for m in bench["per_layer"]}
    for name in TRAIN + OPEN:
        mod = layer_metrics.load(name)
        assert mod.LAYER in layers and name not in listed
        assert mod.SOURCE == ("program_span" if name in OPEN
                              else "device_trace")
        assert mod.MOVES == ("req_p50_s" if name in OPEN
                             else "train_tokens_per_s")
        assert mod.UNIT == ("ms" if name.endswith("_ms") else "%")
        assert mod.__doc__
    for cell in PENDING:
        assert os.path.exists(os.path.join(BENCH, "workloads", f"{cell}.json"))


# --- the CPU rehearsal ---------------------------------------------------------


@pytest.mark.parametrize("cell,reported", [
    ("tiny-pretrain-fsdp4", []), ("tiny-chat-open", OPEN)])
def test_the_rehearsal_cells_stay_correct_with_the_readers_listed(
        cell, reported):
    """No device plane on the CPU: all eight train readers return None and
    the run is `correct`; the engine's idle phase is host-side and reads."""
    proc = run_cell(cell, 1, script=os.path.join("tests", "listed_run.py"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"] == {}
    rehearsed = json.loads(next(
        ln for ln in proc.stderr.splitlines()
        if "rehearsal metrics (not reported): " in ln).split(
            "(not reported): ", 1)[1])
    assert [n for n in PENDING[cell] if n in rehearsed] == reported
    if reported:
        assert 0.0 <= rehearsed[reported[0]]["value"] < 100.0

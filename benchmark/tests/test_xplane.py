"""The trace reduction on a small recorded trace kept beside this file, and
on a trace written by hand: busy/idle share, per-operation time,
exposed-collective time and the idle gaps come out as worked out by hand."""

import json
import os

import pytest

from benchmark.lib import flops, peaks, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def fusion(name, start, dur):
    return (f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128]{{1,0}} %p), kind=kLoop",
            float(start), float(dur))


def op(name, opcode, start, dur):
    return (f"%{name} = f32[8,128]{{1,0}} {opcode}(f32[2,128]{{1,0}} %p), "
            "replica_groups={{0,1}}", float(start), float(dur))


def hand_written():
    """Two chips, a window of 1000 ns (from the first operation at 0 to the
    last end at 1000).

    chip 0: fusion.1 [0,300)  all-gather-done [300,400)  fusion.2 [400,600)
            idle [600,900)  all-reduce [900,1000)
            -> busy 700, idle 300, collectives on the core 100 + 100 = 200
    chip 1: fusion.1 [0,500)  idle [500,700)  fusion.2 [700,1000)
            -> busy 800, idle 200, no collective
    The asynchronous all-gather lives [100,400) on chip 0's async line: total
    collective time there 300 (async) + 100 (the synchronous all-reduce).
    Host: `engine:upload` covers [590,910) and `engine:emit` [480,720).
    """
    return {
        "/device:TPU:0": {
            "XLA Ops": [fusion("fusion.1", 0, 300),
                        op("all-gather-done.1", "all-gather-done", 300, 100),
                        fusion("fusion.2", 400, 200),
                        op("all-reduce.7", "all-reduce", 900, 100)],
            "Async XLA Ops": [op("all-gather-start.1", "all-gather-start", 100, 300)],
        },
        "/device:TPU:1": {
            "XLA Ops": [fusion("fusion.1", 0, 500), fusion("fusion.2", 700, 300)],
        },
        "/host:CPU": {
            "python": [("$threading.py:1000 run", 0.0, 1000.0),
                       ("engine:upload", 590.0, 320.0),
                       ("engine:emit", 480.0, 240.0)],
        },
    }


def test_hand_written_trace(monkeypatch):
    # the hand-written trace counts in nanoseconds: name every gap
    monkeypatch.setattr(xplane, "SHORT_GAP_NS", 0.0)
    r = xplane.reduce(hand_written())
    assert r["chips"] == 2
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((700 + 800) / 2 * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - 1500 / 2000)
    assert r["collective_exposed_s"] == pytest.approx(200 / 2 * 1e-9)
    assert r["collective_total_s"] == pytest.approx(400 / 2 * 1e-9)
    assert r["pallas_s"] == 0.0
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops["fusion.1 bf16[8,128]"] == pytest.approx((300 + 500) / 2 * 1e-9)
    assert ops["fusion.2 bf16[8,128]"] == pytest.approx((200 + 300) / 2 * 1e-9)
    assert ops["all-reduce.7 f32[8,128]"] == pytest.approx(100 / 2 * 1e-9)
    assert r["device_ops"][0][0] == "fusion.1 bf16[8,128]"          # ranked by time
    # chip 0's gap [600,900) lies under engine:upload; chip 1's [500,700)
    # under engine:emit (the shortest host event covering half of it); the
    # thread's run() frame covers both and names neither
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps == {"engine:upload": pytest.approx(300 / 2 * 1e-9),
                    "engine:emit": pytest.approx(200 / 2 * 1e-9)}
    # leaving out the head of the trace: the window becomes [400,1000)
    r = xplane.reduce(hand_written(), skip_head_s=400e-9)
    assert r["window_s"] == pytest.approx(600e-9)
    assert r["busy_s"] == pytest.approx((300 + 400) / 2 * 1e-9)


def test_window_clips_operations():
    r = xplane.reduce(hand_written(), window=(200.0, 800.0))
    # chip 0: [200,600) busy -> 400; chip 1: [200,500) + [700,800) -> 400
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["idle_share"] == pytest.approx(1 - 800 / 1200)
    assert r["collective_exposed_s"] == pytest.approx(100 / 2 * 1e-9)


def test_union_and_names():
    assert xplane.union([(0, 10), (5, 20), (30, 40), (40, 41), (7, 8)]) == [
        (0, 20), (30, 41)]
    name = op("all-gather-start.3", "all-gather-start", 0, 1)[0]
    assert xplane.opcode(name) == "all-gather-start"
    assert xplane.is_collective(name)
    assert not xplane.is_collective(fusion("all-gather-fusion", 0, 1)[0])
    assert xplane.short_name(name) == "all-gather-start.3 f32[8,128]"
    with pytest.raises(ValueError):
        xplane.reduce({"/host:CPU": {"python": []}})


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_probe_trace.json")) as f:
        raw = json.load(f)["trace"]
    return {p: {line: [tuple(e) for e in ev] for line, ev in lines.items()}
            for p, lines in raw.items()}


def test_recorded_v5e_trace(recorded):
    """Two executions of one jitted step on a v5e: 40 operations, of which
    six are Pallas calls. Summed by hand from the event list: the window
    spans 17,108,165 ns, the merged operations 4,989,168 ns, the Pallas calls
    4,163,699 ns, and between the two executions the device waits 12,118,955
    ns while the host sleeps."""
    r = xplane.reduce(recorded)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(17108165e-9, rel=1e-9)
    assert r["busy_s"] == pytest.approx(4989168e-9, rel=1e-9)
    assert r["idle_share"] == pytest.approx(0.7083750, abs=1e-6)
    assert r["pallas_s"] == pytest.approx(4163699e-9, rel=1e-9)
    assert r["collective_exposed_s"] == 0.0
    top_gap, top_gap_s = r["idle_gaps"][0]
    assert top_gap == "$time sleep"
    assert top_gap_s == pytest.approx(12118955e-9, rel=1e-6)
    # the 21 other gaps are a few nanoseconds each: too short to name
    assert r["idle_gaps"][1][0] == xplane.SHORT_GAPS
    assert len(r["idle_gaps"]) == 2
    names = [k for k, _ in r["device_ops"]]
    assert names[0] == "transpose_jvp___.1 bf16[1,16,4096,128] [pallas]"
    assert "convolution_tanh_fusion bf16[2048,4096]" in names
    assert "jvp__.1 [pallas]" in names            # a tuple result: no shape


def test_flash_calls_are_told_apart_and_costed(recorded):
    kinds = {}
    for name, _s, dur in xplane.reduce(recorded)["pallas_events"]:
        shape = xplane.flash_call_shape(name)
        assert shape is not None
        kinds.setdefault(shape["kind"], []).append((shape, dur))
    # forward twice a step (once under grad), dq once; the dkv kernel's
    # result is unused in the probe and was compiled away
    assert {k: len(v) for k, v in kinds.items()} == {"fwd": 4, "dq": 2}
    shape, dur = kinds["fwd"][0]
    assert shape == {"kind": "fwd", "batch": 1, "heads": 16, "kv_heads": 8,
                     "sq": 4096, "sk": 4096, "hd": 128, "itemsize": 2}
    cost = flops.flash_kernel_cost(**shape)
    # 2 matmuls x 2 ops x 16 heads x 4096^2 x 128 x 1/2 (causal)
    assert cost["flops"] == 2 * 2 * 16 * 4096 * 4096 * 128 / 2
    roof = flops.roofline_seconds(cost, peaks.peaks_for("TPU v5 lite"))
    assert roof["bound"] == "compute"
    assert 0.4 < roof["seconds"] / (dur * 1e-9) < 0.6   # 51% on the chip
    # the dkv call as ops/flash_attention.py makes it (not in the probe): one
    # dk/dv pair per query head, lse and delta rows as [b, h, 1, s]
    dkv = ('%x.1 = (bf16[1,16,4096,128]{3,2,1,0}, bf16[1,16,4096,128]{3,2,1,0}) '
           'custom-call(bf16[1,16,4096,128]{3,2,1,0} %q, bf16[1,8,4096,128]{3,2,1,0} %k, '
           'bf16[1,8,4096,128]{3,2,1,0} %v, bf16[1,16,4096,128]{3,2,1,0} %g, '
           'f32[1,16,1,4096]{3,2,1,0} %lse, f32[1,16,1,4096]{3,2,1,0} %d), '
           'custom_call_target="tpu_custom_call"')
    assert xplane.flash_call_shape(dkv) == {**shape, "kind": "dkv"}
    assert flops.flash_kernel_cost(**{**shape, "kind": "dkv"})["flops"] == 2 * cost["flops"]
    # a call with another signature is not costed as flash attention
    assert xplane.flash_call_shape(
        '%x = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %a), '
        'custom_call_target="tpu_custom_call"') is None
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


def test_flops_of_the_two_configurations():
    def hp(name):
        with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
            return json.load(f)

    intern, mistral = hp("internlm2-1.8b"), hp("mistral-7b-v0.3-l16")
    # 2048x(2048 + 2x1024 + 2048) + 3x2048x8192 = 62.9M a layer; head 189.5M
    assert flops.matmul_params(intern) == 24 * 62914560 + 2048 * 92544
    assert flops.total_params(intern) == pytest.approx(1.889e9, rel=1e-3)
    assert flops.total_params(mistral) == pytest.approx(3.758e9, rel=1e-3)
    per_token = flops.train_flops_per_token(intern, 4096)
    # 6 x 1.70e9 + 3 x (4 x 16 x 128 x 4096 / 2) x 24 = 10.20e9 + 1.21e9
    assert per_token == pytest.approx(11.41e9, rel=2e-3)

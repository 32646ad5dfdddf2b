"""What makes a serve run not `correct` besides a wrong answer: a late
generator and a client-side token rate that leaves the engine's own count.
Both limits are the runner's; no traffic file can loosen them."""

from types import SimpleNamespace

import pytest

from benchmark.runners import serve_dp

WINDOW = 50.0


def ctx_of(loop, rehearsal=False, traffic=None):
    return SimpleNamespace(generator=SimpleNamespace(LOOP=loop),
                           rehearsal=rehearsal, traffic=traffic or {})


def art_of(records, tokens_out=0):
    return {"t_open": 100.0, "window_s": WINDOW, "records": records,
            "check": {"ok": True}, "cache_files_open": 3, "cache_files_close": 3,
            "stats_open": {"tokens_out": 1000},
            "stats_close": {"tokens_out": 1000 + tokens_out}}


def closed_records(callers=4, every=5.0, tokens=10, wait=0.0):
    """Callers answering back to back from before the window to after it: a
    request takes `every` seconds, of which the first `wait` are spent
    queueing, and returns `tokens`."""
    recs = []
    for c in range(callers):
        t = 80.0 + c * every / callers
        while t < 100.0 + WINDOW + every:
            recs.append({"tag": "w", "due": t, "sent": t, "done": t + every,
                         "ok": True, "error": None, "tokens": tokens})
            t += every
    return recs


def test_closed_loop_estimate_agrees_with_the_count_and_is_held_to_it():
    recs = closed_records()
    truth = 4 * 10 / 5.0 * WINDOW          # tokens really generated in the window
    art = serve_dp.finish(ctx_of("closed"), art_of(recs, tokens_out=round(truth)))
    assert art["problems"] == []
    assert art["end_to_end"]["out_tokens_per_s"] == pytest.approx(8.0, rel=1e-9)
    # the engine counted 5% fewer tokens than the client's estimate claims
    art = serve_dp.finish(ctx_of("closed"),
                          art_of(recs, tokens_out=round(truth / 1.05)))
    assert any("tokens_out" in p for p in art["problems"])
    # a counter that did not move cannot confirm anything
    art = serve_dp.finish(ctx_of("closed"), art_of(recs, tokens_out=0))
    assert any("tokens_out" in p for p in art["problems"])


def test_a_traffic_file_cannot_loosen_the_limits():
    """Whatever keys a traffic file carries, the limits are the runner's;
    only a rehearsal cell (never in BENCHMARK.json) gets the looser ones."""
    recs = closed_records()
    inside = next(r for r in recs if 110.0 < r["sent"] < 120.0)
    inside["sent"] += 0.3                    # one send 0.3 s late, request 5 s
    loose = {"late_limit_share": 1.0, "drain_cap_s": 1.0, "counter_share": 1.0}
    art = serve_dp.finish(ctx_of("closed", traffic=loose),
                          art_of(recs, tokens_out=round(400 / 1.05)))
    assert any("ran late" in p for p in art["problems"])
    assert any("tokens_out" in p for p in art["problems"])
    art = serve_dp.finish(ctx_of("closed", rehearsal=True, traffic=loose),
                          art_of(recs, tokens_out=round(400 / 1.05)))
    assert art["problems"] == []


def test_open_loop_is_judged_on_the_worst_lateness():
    recs = [{"tag": "w", "due": 100.0 + i * 0.5, "sent": 100.0 + i * 0.5 + 0.001,
             "done": 100.0 + i * 0.5 + 10.0, "ok": True, "error": None,
             "tokens": 64} for i in range(100)]
    art = serve_dp.finish(ctx_of("open"), art_of(recs))
    assert art["problems"] == [] and art["attempted"] == 100
    assert art["end_to_end"]["req_p50_s"] == pytest.approx(10.0)
    assert "counter_tokens_per_s" not in art      # open loop: nothing to hold
    recs[40]["sent"] += 0.6                        # 6% of the median request
    art = serve_dp.finish(ctx_of("open"), art_of(recs))
    assert any("ran late" in p and "601.0 ms" in p for p in art["problems"])

"""Each generator is a pure function of --seed and its parameters, the open
loop is timed from due times, and the length distributions have the stated
medians and clips."""

import itertools
import json
import os

import numpy as np
import pytest

from benchmark.lib import stats
from benchmark.traffic import (_common, closed_clients, closed_sessions,
                               open_poisson, train_synthetic)

HERE = os.path.dirname(os.path.abspath(__file__))


def traffic(name):
    with open(os.path.join(HERE, "..", "traffic", f"{name}.json")) as f:
        return json.load(f)


def take(stream, n):
    return list(itertools.islice(stream, n))


def test_open_poisson_is_a_pure_function_of_the_seed():
    p = traffic("chat-open")
    a, b, c = (open_poisson.schedule(p, s, 30.0) for s in (7, 7, 8))
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    assert [r["due"] for r in a] != [r["due"] for r in c]


def test_open_poisson_counts_spans_and_due_times():
    p = traffic("chat-open")
    sched = open_poisson.schedule(p, 3, 30.0)
    by_tag = {t: [r for r in sched if r["tag"] == t] for t in "rwt"}
    assert len(by_tag["w"]) == round(p["rate_per_s"] * 30.0)
    assert len(by_tag["r"]) == round(p["rate_per_s"] * p["ramp_s"])
    assert all(-p["ramp_s"] <= r["due"] < 0 for r in by_tag["r"])
    assert all(0 <= r["due"] < 30.0 for r in by_tag["w"])
    assert all(30.0 <= r["due"] < 60.0 for r in by_tag["t"])
    due = [r["due"] for r in sched]
    assert due == sorted(due)
    # every seed offers the same multiset of lengths: only the order differs
    other = [r for r in open_poisson.schedule(p, 4, 30.0) if r["tag"] == "w"]
    for key in ("prompt_tokens", "max_tokens"):
        assert sorted(r[key] for r in by_tag["w"]) == sorted(r[key] for r in other)
        assert [r[key] for r in by_tag["w"]] != [r[key] for r in other]


@pytest.mark.parametrize("name,key,spec_key", [
    ("chat-open", "prompt_tokens", "prompt_tokens"),
    ("chat-open", "max_tokens", "answer_tokens"),
])
def test_length_distributions_have_the_stated_median_and_clips(name, key, spec_key):
    p = traffic(name)
    spec = p[spec_key]
    lens = [r[key] for r in open_poisson.schedule(p, 1, 100.0) if r["tag"] == "w"]
    assert min(lens) >= spec["min"] and max(lens) <= spec["max"]
    assert min(lens) == spec["min"] and max(lens) == spec["max"]  # both tails reach the clip
    assert abs(stats.median(lens) - spec["median"]) <= 0.03 * spec["median"]
    # sigma: the quartiles of a log-normal sit at exp(+-0.6745 sigma)
    q75 = stats.percentile(lens, 75.0) / spec["median"]
    assert abs(np.log(q75) - 0.6745 * spec["sigma"]) < 0.05


def test_prompts_tokenise_to_the_stated_length_and_share_no_first_block():
    p = traffic("chat-open")
    sched = open_poisson.schedule(p, 5, 20.0)
    for r in sched:
        assert r["prompt"].isascii()
        assert len(r["prompt"].encode()) + 1 == r["prompt_tokens"]
    first_blocks = [r["prompt"][:15] for r in sched]
    assert len(set(first_blocks)) == len(first_blocks)


@pytest.mark.parametrize("gen,name", [(closed_clients, "chat-closed"),
                                      (closed_sessions, "docqa-closed")])
def test_closed_generators_are_pure_functions_of_seed_and_client(gen, name):
    p = traffic(name)
    a, b = take(gen.stream(p, 11, 2), 9), take(gen.stream(p, 11, 2), 9)
    assert a == b
    assert a != take(gen.stream(p, 12, 2), 9)
    assert a != take(gen.stream(p, 11, 3), 9)
    for r in a:
        assert len(r["prompt"].encode()) + 1 == r["prompt_tokens"]


def test_closed_clients_deal_one_pool_whatever_the_seed():
    p = traffic("chat-closed")
    per_client = p["pool"] // p["clients"]

    def multiset(seed):
        return sorted(r["max_tokens"] for c in range(p["clients"])
                      for r in take(closed_clients.stream(p, seed, c), per_client))

    assert multiset(1) == multiset(2)
    assert stats.median(multiset(1)) == pytest.approx(
        p["answer_tokens"]["median"], rel=0.03)


def test_sessions_share_their_document_and_nothing_else():
    p = traffic("docqa-closed")
    per = p["questions_per_session"]
    reqs = take(closed_sessions.stream(p, 21, 0), 2 * per)
    first, second = reqs[:per], reqs[per:]
    assert [r["turn"] for r in first] == list(range(per))
    assert len({r["session"] for r in first}) == 1
    doc_len = len(os.path.commonprefix([r["prompt"] for r in first]))
    d, q = p["document_tokens"], p["question_tokens"]
    assert d["min"] - 1 <= doc_len <= d["max"] + 8
    for r in first:
        assert d["min"] + q["min"] <= r["prompt_tokens"] <= d["max"] + q["max"]
        assert r["max_tokens"] == p["answer_tokens"]
    # a new session is a new document from its first block on
    assert first[0]["prompt"][:15] != second[0]["prompt"][:15]
    other = take(closed_sessions.stream(p, 21, 1), 1)[0]
    assert other["prompt"][:15] != first[0]["prompt"][:15]


def test_train_batches_are_a_function_of_seed_and_step():
    p = traffic("tiny-pretrain")
    fn = train_synthetic.batch_fn(p, vocab_size=512, chips=4)
    a, b = np.asarray(fn(1, 0)), np.asarray(fn(1, 0))
    assert a.shape == (4, p["seq_len"]) and a.dtype == np.int32
    assert (a == b).all()
    assert (a != np.asarray(fn(1, 1))).any() and (a != np.asarray(fn(2, 0))).any()
    assert a.min() >= 0 and a.max() < 512
    # Zipf-like: low ids are much more frequent than high ones
    big = np.concatenate([np.asarray(fn(3, s)).ravel() for s in range(8)])
    assert (big < 8).mean() > 3 * ((big >= 256) & (big < 264)).mean()
    assert train_synthetic.tokens_per_step(p, 4) == 4 * p["seq_len"]


def test_stratified_lengths_and_percentile_rule():
    # the rule: a percentile is reported only with ten samples beyond it
    spec = {"dist": "uniform", "min": 10, "max": 20}
    lens = _common.stratified_lengths(101, spec)
    assert lens.min() == 10 and lens.max() == 20 and stats.median(list(lens)) == 15
    # the highest percentile with ten samples beyond it
    assert stats.samples_beyond(150, 90.0) == 15 and stats.samples_beyond(90, 90.0) == 9
    assert stats.samples_beyond(92, 90.0) == 10    # the open cell at 1.8/s x 51 s
    assert stats.percentile([1, 2, 3, float("inf")], 50) == 2.5
    assert stats.percentile([1, 2, float("inf")], 90) == float("inf")


def test_open_loop_times_each_request_from_its_due_time():
    """A stalled generator must show as latency and as lateness: the open
    loop's record keeps `due`, and the latency the runner reports is
    done - due, not done - sent."""
    import asyncio

    from benchmark.runners import serve_dp

    class SlowLoad(serve_dp.Load):
        async def send(self, req, due):
            rec = {"tag": req["tag"], "due": due, "sent": serve_dp.time.monotonic(),
                   "prompt_tokens": 1, "max_tokens": 1}
            self.records.append(rec)
            await asyncio.sleep(0.01)
            rec.update(done=serve_dp.time.monotonic(), ok=True, error=None,
                       tokens=1, token_ids=[0])
            return rec

    async def go():
        load = SlowLoad("unused")
        t_open = serve_dp.time.monotonic() - 0.2   # the schedule is 0.2 s behind
        sched = [{"due": 0.0, "tag": "w"}, {"due": 0.05, "tag": "w"}]
        await serve_dp.open_loop(load, sched, t_open)
        return t_open, load.records

    t_open, recs = asyncio.run(go())
    assert [r["due"] for r in recs] == [t_open, t_open + 0.05]
    for r in recs:
        assert r["sent"] - r["due"] >= 0.14          # lateness is visible
        assert r["done"] - r["due"] >= 0.15          # and counted in the latency

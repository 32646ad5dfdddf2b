"""Open loop: independent users. Arrivals are a Poisson process at a fixed
rate, sent on schedule whether or not earlier requests have finished.

A pure function of (`--seed`, parameters). Parameters (the traffic file):
  rate_per_s   offered requests per second (fixed in the cell, never searched)
  ramp_s       seconds of the same traffic before the window opens (set-up)
  prompt_tokens, answer_tokens   length specs (see _common.stratified_lengths)

The number of arrivals in a span is fixed at rate x span and their times are
sorted uniform draws: a Poisson process conditioned on its count, so that
every seed offers the same number of requests.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.traffic import _common as c

LOOP = "open"


def _span(params: Dict, seed: int, stream: int, t0: float, t1: float,
          tag: str) -> List[Dict]:
    rng = c.rng_for(seed, stream)
    n = int(round(params["rate_per_s"] * (t1 - t0)))
    due = sorted(float(t) for t in rng.uniform(t0, t1, size=n))
    p_len = c.shuffled_lengths(rng, n, params["prompt_tokens"])
    a_len = c.shuffled_lengths(rng, n, params["answer_tokens"])
    return [c.request(c.prompt_of(rng, p_len[i], f"{tag}{seed:x}.{i:x}"),
                      a_len[i], due=due[i], tag=tag) for i in range(n)]


def schedule(params: Dict, seed: int, window_s: float) -> List[Dict]:
    """Every request with its due time in seconds relative to the opening of
    the window: the ramp (due < 0, tag "r"), the window (tag "w") and the
    tail (tag "t": the same traffic for as long again, sent only while
    requests of the window are still in flight, so that they finish under
    load)."""
    return (_span(params, seed, 1, -float(params["ramp_s"]), 0.0, "r")
            + _span(params, seed, 2, 0.0, window_s, "w")
            + _span(params, seed, 3, window_s, 2.0 * window_s, "t"))

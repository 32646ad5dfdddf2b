"""Closed loop: a fixed number of callers, each sending its next request when
the last one is answered. A slow system receives less load, so what is judged
is the work completed, not the latency.

A pure function of (`--seed`, parameters, client index). Parameters:
  clients        concurrent callers
  ramp_s         seconds the callers run before the window opens (set-up)
  pool           requests in the stratified pool the callers draw from
  prompt_tokens, answer_tokens   length specs
"""

from __future__ import annotations

from typing import Dict, Iterator

from benchmark.traffic import _common as c

LOOP = "closed"


def stream(params: Dict, seed: int, client: int) -> Iterator[Dict]:
    """The endless sequence of requests of one caller. The pool's lengths
    are shuffled once per seed and dealt round-robin, so the callers
    together send a fixed multiset whatever their pace."""
    n, clients = int(params["pool"]), int(params["clients"])
    rng = c.rng_for(seed, 0)
    p_len = c.shuffled_lengths(rng, n, params["prompt_tokens"])
    a_len = c.shuffled_lengths(rng, n, params["answer_tokens"])
    text_rng = c.rng_for(seed, 1, client)
    i = client
    while True:
        j = i % n
        yield c.request(
            c.prompt_of(text_rng, p_len[j], f"c{seed:x}.{i:x}"), a_len[j],
            tag="w")
        i += clients

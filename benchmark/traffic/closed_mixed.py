"""Closed loop with several classes of caller in one queue: each caller
belongs to one class and sends that class's requests, its next when the last
is answered. The classes share the engine, its slots and its queue, so a
short request waits behind the long ones' prompts.

A pure function of (`--seed`, parameters, client index). Parameters:
  clients   concurrent callers, the sum of the classes'
  ramp_s    seconds the callers run before the window opens (set-up)
  classes   a list, in the callers' order, of
    name, clients   the class's callers: the next `clients` indices
    pool            requests in the class's stratified pool
    prompt_tokens, answer_tokens   length specs (`_common.stratified_lengths`)

Each class's pool is shuffled once per seed and dealt round-robin among its
own callers, so the class sends a fixed multiset whatever its callers' pace.
Prompts are salted as `closed_clients` salts them: no two share a first block.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

from benchmark.traffic import _common as c

LOOP = "closed"


def class_of(params: Dict, client: int) -> Tuple[int, Dict, int]:
    """(the class's index, the class, the caller's index within it)."""
    first = 0
    for k, cls in enumerate(params["classes"]):
        if client < first + int(cls["clients"]):
            return k, cls, client - first
        first += int(cls["clients"])
    raise ValueError(f"caller {client} of {first}: the classes' callers must "
                     "add up to `clients`")


def stream(params: Dict, seed: int, client: int) -> Iterator[Dict]:
    """The endless sequence of requests of one caller."""
    assert sum(int(k["clients"]) for k in params["classes"]) == int(
        params["clients"])
    k, cls, i = class_of(params, client)
    n, callers = int(cls["pool"]), int(cls["clients"])
    rng = c.rng_for(seed, 0, k)
    p_len = c.shuffled_lengths(rng, n, cls["prompt_tokens"])
    a_len = c.shuffled_lengths(rng, n, cls["answer_tokens"])
    text_rng = c.rng_for(seed, 1, client)
    while True:
        j = i % n
        yield c.request(
            c.prompt_of(text_rng, p_len[j], f"{cls['name'][0]}{seed:x}.{i:x}"),
            a_len[j], tag="w", kind=cls["name"])
        i += callers

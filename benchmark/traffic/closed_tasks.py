"""Closed loop of many-shot tasks: a fixed number of callers in lanes, each
lane working through its own sequence of tasks. A task is one long header
(the shots) shared by many items; a request's prompt is header + item. The
lane's callers take the task's items round-robin, each sending its next when
its last is answered, and go on to the lane's next task, with a new header,
when the items run out. The header's blocks are what the prefix cache can
reuse; a new task's are cold once.

A pure function of (`--seed`, parameters, client index). Parameters:
  clients, ramp_s    as in closed_clients
  lanes              lane = client mod lanes; clients / lanes callers a lane
  pool               tasks in the stratified pool the lanes draw from
  items_per_task     items behind one header
  header_tokens, item_tokens, answer_tokens   length specs
Lane l starts `items_per_task * l / lanes` items into its first task, so
that the lanes' turnovers are spread evenly. A client index past `clients`
(a check's) is a lane of its own, from its task's first item.
"""

from __future__ import annotations

from typing import Dict, Iterator

from benchmark.traffic import _common as c

LOOP = "closed"


def stream(params: Dict, seed: int, client: int) -> Iterator[Dict]:
    n, clients = int(params["pool"]), int(params["clients"])
    lanes, per = int(params["lanes"]), int(params["items_per_task"])
    rng = c.rng_for(seed, 0)
    h_len = c.shuffled_lengths(rng, n, params["header_tokens"])
    i_len = c.shuffled_lengths(rng, n * per, params["item_tokens"])
    a_len = c.shuffled_lengths(rng, n * per, params["answer_tokens"])
    if client < clients:
        lane, rank, width = client % lanes, client // lanes, clients // lanes
        at = per * lane // lanes + rank
    else:
        lane, width, at = client, 1, 0
    header, task = None, -1
    while True:
        t, i = divmod(at, per)
        # the lane's t-th task: lanes draw disjoint tasks of the pool
        j = (lane + lanes * t) % n
        if t != task:
            task = t
            header = c.prompt_of(c.rng_for(seed, 1, lane, t), h_len[j],
                                 f"t{seed:x}.{lane:x}.{t:x}")
        item = c.text(c.rng_for(seed, 2, lane, t, i), i_len[j * per + i],
                      head=f" I{i:x}: ")
        yield c.request(header + item, a_len[j * per + i], tag="w",
                        lane=lane, task=t, item=i)
        at += width

"""Closed loop of agent sessions behind shared system prompts: each caller
holds a conversation of several turns with one tenant's backend. Every
prompt opens with the tenant's long system prompt (policies, tool
specifications, a catalogue), which every session of the tenant shares; turn
k's prompt is that plus the k pieces appended so far, each piece standing for
the previous answer's transcript and the next tool result or user message.
After the last turn the caller starts a new conversation: the same system
prompt, fresh pieces. A system prompt's blocks and a conversation's own
earlier turns are what the prefix cache can reuse; every piece leads with a
salt of its own, so nothing else is ever shared.

A pure function of (`--seed`, parameters, client index). Parameters:
  clients, ramp_s    as in closed_clients
  tenants            system prompts; caller i talks to tenant i mod tenants
  system_tokens      a system prompt's length in tokens (its BOS included)
  turns              turns a conversation
  pool               pieces (and answers) in the stratified pool: one round of
                     conversations, clients x turns
  piece_tokens, answer_tokens   length specs
Caller i's first conversation begins with i mod turns pieces already in its
prompt, so that a window sees every turn number at once. In round r caller i
takes the pool's slots of caller (i + 5 r) mod clients: a round sends the
whole pool once, whatever the seed. A client index past `clients` (a check's,
a warm-up's) is a session of its own from its conversation's first turn.
"""

from __future__ import annotations

from typing import Dict, Iterator

from benchmark.traffic import _common as c

LOOP = "closed"


def system_prompt(params: Dict, seed: int, tenant: int) -> str:
    return c.prompt_of(c.rng_for(seed, 1, tenant),
                       int(params["system_tokens"]), f"sys{seed:x}.{tenant:x}")


def stream(params: Dict, seed: int, client: int) -> Iterator[Dict]:
    """The endless sequence of requests of one caller."""
    n, clients = int(params["pool"]), int(params["clients"])
    turns = int(params["turns"])
    assert n == clients * turns, "the pool is one round of conversations"
    rng = c.rng_for(seed, 0)
    p_len = c.shuffled_lengths(rng, n, params["piece_tokens"])
    a_len = c.shuffled_lengths(rng, n, params["answer_tokens"])
    system = system_prompt(params, seed, client % int(params["tenants"]))
    first = client % turns if client < clients else 0
    conv = 0
    while True:
        base = ((client + 5 * conv) % clients) * turns
        pieces = [c.text(c.rng_for(seed, 2, client, conv, k), p_len[base + k],
                         head=f" [{seed:x}.{client:x}.{conv:x}.{k:x}] ")
                  for k in range(turns)]
        for k in range(first, turns):
            yield c.request(system + "".join(pieces[: k + 1]), a_len[base + k],
                            tag="w", session=client, conversation=conv, turn=k)
        first, conv = 0, conv + 1

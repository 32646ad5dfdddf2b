"""Closed loop of sessions: each caller draws a document, asks several
questions about it one after the other (prompt = document + question), then
starts a new session with a new document. The document's KV blocks are what
the prefix cache can reuse; a new session's are cold.

A pure function of (`--seed`, parameters, client index). Parameters:
  clients, ramp_s, pool   as in closed_clients (pool counts sessions)
  document_tokens, question_tokens   length specs
  questions_per_session, answer_tokens (a fixed number)
"""

from __future__ import annotations

from typing import Dict, Iterator

from benchmark.traffic import _common as c

LOOP = "closed"


def stream(params: Dict, seed: int, client: int) -> Iterator[Dict]:
    n, clients = int(params["pool"]), int(params["clients"])
    per = int(params["questions_per_session"])
    rng = c.rng_for(seed, 0)
    d_len = c.shuffled_lengths(rng, n, params["document_tokens"])
    q_len = c.shuffled_lengths(rng, n * per, params["question_tokens"])
    text_rng = c.rng_for(seed, 1, client)
    s = client
    while True:
        j = s % n
        # the document includes the BOS token; the question follows it
        doc = c.prompt_of(text_rng, d_len[j], f"d{seed:x}.{s:x}")
        for k in range(per):
            q = c.text(text_rng, q_len[j * per + k], head=f" Q{k}: ")
            yield c.request(doc + q, params["answer_tokens"], tag="w",
                            session=s, turn=k)
        s += clients

"""What `_inside.py` is to the Llama block, for the JoyAI-LLM-Flash family:
the one place that knows the names `ray_tpu.models.joyai` gives its
parameters (the reference, lib/reference_joyai.py, knows the published ones),
and the reference check that runs where the weights are.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

import numpy as np

# published name -> program name
LAYER_NAMES = {"input_layernorm": "ln1", "post_attention_layernorm": "ln2",
               "q_a_proj": "wqa", "q_a_layernorm": "q_norm",
               "q_b_proj": "wqb", "kv_a_proj_with_mqa": "wkva",
               "kv_a_layernorm": "kv_norm", "kv_b_proj": "wkvb",
               "o_proj": "wo"}
DENSE_NAMES = {"gate_proj": "w1", "up_proj": "w3", "down_proj": "w2"}
MOE_NAMES = {"gate": "router", "e_score_correction_bias": "router_bias",
             "shared_gate_proj": "sh_w1", "shared_up_proj": "sh_w3",
             "shared_down_proj": "sh_w2"}
EXPERT_NAMES = {"gate_proj": "e_w1", "up_proj": "e_w3", "down_proj": "e_w2"}


class ProgramWeightsJoyAI:
    """The reference's view of the program's parameter tree: one layer at a
    time, cast to float32; the held experts a block at a time. The program
    keeps its leading dense layers as a list and stacks the expert layers."""

    def __init__(self, params: Dict[str, Any]):
        self.params = params
        self.n_dense = len(params["dense"])

    @staticmethod
    def _f32(x):
        import jax.numpy as jnp

        return x.astype(jnp.float32)

    def embed(self, tokens):
        return self._f32(self.params["tok_emb"][tokens])

    def layer(self, i: int) -> Dict[str, Any]:
        if i < self.n_dense:
            p, names = self.params["dense"][i], {**LAYER_NAMES, **DENSE_NAMES}
            return {ref: self._f32(p[prog]) for ref, prog in names.items()}
        m, p = i - self.n_dense, self.params["layers"]
        return {ref: self._f32(p[prog][m])
                for ref, prog in {**LAYER_NAMES, **MOE_NAMES}.items()}

    def experts(self, i: int, lo: int, hi: int) -> Dict[str, Any]:
        p = self.params["layers"]
        return {ref: self._f32(p[prog][i - self.n_dense, lo:hi])
                for ref, prog in EXPERT_NAMES.items()}

    def routers(self) -> List[Any]:
        """W_r of each expert layer, float32."""
        return list(self._f32(self.params["layers"]["router"]))

    def router_norms(self) -> List[np.ndarray]:
        """|W_r[:, e]| of each expert layer, for the routing margin's step."""
        return list(np.linalg.norm(np.asarray(
            self.params["layers"]["router"], np.float32), axis=1))

    def final_norm(self):
        return self._f32(self.params["norm"])

    def head(self):
        return self._f32(self.params["lm_head"])


def part_at(got: List[int], want: List[int]) -> int:
    """The first index at which two answers differ; -1 where they do not."""
    for i, (a, b) in enumerate(zip(got, want)):
        if a != b:
            return i
    return -1 if len(got) >= len(want) else len(got)


async def engine_reference_check(actor, hp: Dict[str, Any],
                                 samples: List[Dict[str, Any]],
                                 pad_multiple: int, *, config: Dict[str, Any],
                                 system_tokens: int, scopes_path: str = None,
                                 second_readings: bool = False,
                                 plant: str = None) -> List[Dict[str, Any]]:
    """`_inside.engine_reference_check` for one conversation: `samples` are
    the turns of one session, in order, as the served path answered them, each
    prompt the one before plus a piece. Turn 1 was served from the blocks
    another session's prompt left (its system prompt), the later turns from
    their own earlier turns'.

    Each turn is run once more through the engine's own loop with its routing
    recorded (`check_routing`): the first and the last *cold*, from position
    0 whatever is cached, the others over the cached blocks as they were
    served. `replays_part_at` says where each replay's tokens part from the
    served turn's (-1: nowhere). On the chip they do part: a replay runs a
    prompt's last rows in another chunk width, another program, than the
    served turn did, and seeded weights' greedy tokens are near ties. So the
    conversation the reference is fed, once, from position 0
    (`reference_joyai.conversation_gaps`), is the last prompt and the last
    *replay's* answer, whose every position has the program's own experts
    (the cold replay's routing), and what is held to its logits is: every
    turn's first served token (its history is the prompt alone), the last
    turn's served tokens up to and with the one at which the replay parts
    (they share the replay's history), and every token of the last replay,
    which the loop's own programs drew. Beside them the routing margins, and
    the pool's rows of the conversation's cached blocks (found under its
    prompt's chain keys in the prefix cache, where the served turns left
    them) against the reference's latents, layer by layer. The routers'
    scores at the replays' decode steps are held to the float32 router on
    the same inputs.

    `block_hits`: the blocks the prefix cache served so far against the least
    the served session must have been given (the system prompt but its last
    block, then each previous prompt's whole blocks).

    A traced run (`scopes_path`) also writes the scope of every instruction
    of the engine's compiled steps (lib/scopes.py), at every chunk width."""
    import json

    import jax.numpy as jnp

    from benchmark.lib import reference_joyai as ref
    from benchmark.lib import reference_ling as rl
    from benchmark.lib import scopes
    from ray_tpu.llm._prefix_cache import chain_keys

    engine = actor.engine
    if scopes_path:
        texts = await asyncio.to_thread(engine.step_hlo, [])
        with open(scopes_path, "w") as f:
            json.dump({program: scopes.instruction_scopes(hlos)
                       for program, hlos in texts.items()}, f)
    bs, cache = engine.bs, engine._prefix_cache
    plens = [len(s["prompt_ids"]) for s in samples]
    hits_least = (system_tokens - bs) // bs + sum(p // bs for p in plens[:-1])
    block_hits = cache.block_hits if cache is not None else 0
    t0 = time.monotonic()
    replays = []
    for i, s in enumerate(samples):
        replays.append(await engine.check_routing(
            s["prompt_ids"], max(2, len(s["answer_ids"])), mechanisms=True,
            cold=i in (0, len(samples) - 1)))
    t_replay = time.monotonic() - t0
    weights = ProgramWeightsJoyAI(engine.params)
    last, final = samples[-1], replays[-1]
    served, n = list(last["answer_ids"]), len(last["answer_ids"])
    replayed = list(final["token_ids"][:n])
    parts = [part_at(list(r["token_ids"]), list(s["answer_ids"]))
             for s, r in zip(samples, replays)]
    tokens = list(last["prompt_ids"]) + replayed
    for s in samples:
        assert tokens[: len(s["prompt_ids"])] == list(s["prompt_ids"]), (
            "every turn's prompt opens the last turn's")
    judged = [(p - 1, s["answer_ids"][0])
              for p, s in zip(plens[:-1], samples) if s["answer_ids"]]
    shared = n if parts[-1] < 0 else parts[-1] + 1
    judged += [(plens[-1] - 1 + j, t) for j, t in enumerate(served[:shared])]
    judged += [(plens[-1] - 1 + j, t) for j, t in enumerate(replayed)]
    # the pool's rows of the conversation, through the blocks its prompt's
    # chain keys name
    blocks = []
    if cache is not None:
        blocks = cache.match(chain_keys(last["prompt_ids"], bs))
        cache.cancel_match(blocks)
    at = np.asarray(blocks, np.int32)

    def cached(layer: int):
        rows = np.asarray(engine.latents[layer, at], np.float32)
        return rows.reshape(len(blocks) * bs, -1)[:, : hp["kv_lora_rank"]
                                                  + hp["qk_rope_head_dim"]]

    def run():
        t0 = time.monotonic()
        g = ref.conversation_gaps(
            config, weights, tokens, judged,
            final["routing"][:, : plens[-1] + max(n, 1) - 1],
            cached if blocks else None, pad_multiple,
            second_readings=second_readings, plant=plant)
        g["replays_part_at"] = parts
        g["served_tokens_judged"] = len(samples) - 1 + shared
        g["prompt_tokens"] = plens
        g["cached_positions"] = len(blocks) * bs
        g["cached_positions_least"] = (plens[-1] - 1) // bs * bs
        g["block_hits"], g["block_hits_least"] = block_hits, hits_least
        g["router_f32_steps"] = g["router_f32_steps_bf16"] = 0.0
        routers = weights.routers()
        for r in replays:
            if "router_x" not in r:
                continue        # the answer ended before a decode step
            for m, w in enumerate(routers):
                x, s = r["router_x"][:, m], r["router_s"][:, m]
                g["router_f32_steps"] = max(
                    g["router_f32_steps"], rl.router_float32_steps(x, s, w))
                if second_readings:
                    g["router_f32_steps_bf16"] = max(
                        g["router_f32_steps_bf16"], rl.router_float32_steps(
                            x, rl.router_scores_in(x, w, jnp.bfloat16), w))
        g["seconds"] = {"replay": t_replay,
                        "reference": time.monotonic() - t0}
        return [g]

    return await asyncio.to_thread(run)

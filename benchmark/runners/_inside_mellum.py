"""What `_inside.py` is to the Llama block, for the Mellum 2 family: the one
place that knows the names `ray_tpu.models.mellum` gives its parameters (the
reference, lib/reference_mellum.py, knows its own), and the reference check
that runs where the weights are.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

import numpy as np

# reference name -> program name
LAYER_NAMES = {"attn_norm": "ln1", "ffn_norm": "ln2", "q_norm": "q_norm",
               "k_norm": "k_norm", "wo": "wo", "router": "router"}
EXPERT_NAMES = {"w_gate": "e_w1", "w_up": "e_w3", "w_down": "e_w2"}


class ProgramWeightsMellum:
    """The reference's view of the program's parameter tree: one layer at a
    time, cast to float32; the experts a block at a time. `kv_width`:
    kv_heads x head_dim, where the packed leaf [q | k | v] is cut."""

    def __init__(self, params: Dict[str, Any], kv_width: int):
        self.params, self.kv_width = params, kv_width

    @staticmethod
    def _f32(x):
        import jax.numpy as jnp

        return x.astype(jnp.float32)

    def embed(self, tokens):
        return self._f32(self.params["tok_emb"][tokens])

    def layer(self, i: int) -> Dict[str, Any]:
        p = self.params["layers"][i]
        nq = p["wqkv"].shape[1] - 2 * self.kv_width
        out = {"wq": self._f32(p["wqkv"][:, :nq]),
               "wk": self._f32(p["wqkv"][:, nq:nq + self.kv_width]),
               "wv": self._f32(p["wqkv"][:, nq + self.kv_width:])}
        out.update({ref: self._f32(p[prog])
                    for ref, prog in LAYER_NAMES.items()})
        return out

    def experts(self, i: int, lo: int, hi: int) -> Dict[str, Any]:
        p = self.params["layers"][i]
        return {ref: self._f32(p[prog][lo:hi])
                for ref, prog in EXPERT_NAMES.items()}

    def routers(self) -> List[Any]:
        """W_r of each layer, float32."""
        return [self._f32(p["router"]) for p in self.params["layers"]]

    def router_norms(self) -> List[np.ndarray]:
        """|W_r[:, e]| of each layer, for the routing margin's step."""
        return [np.linalg.norm(np.asarray(p["router"], np.float32), axis=0)
                for p in self.params["layers"]]

    def final_norm(self):
        return self._f32(self.params["norm"])

    def head(self):
        return self._f32(self.params["lm_head"])


async def engine_reference_check(actor, hp: Dict[str, Any],
                                 samples: List[Dict[str, Any]],
                                 pad_multiple: int, *, config: Dict[str, Any],
                                 scopes_path: str = None,
                                 plants: List[str] = ()
                                 ) -> List[Dict[str, Any]]:
    """`_inside.engine_reference_check` for a model with routed experts and
    two kinds of attention. Each sample is run once more through the
    engine's own loop (its prompt as chunks, then decode steps through the
    block pool and the slot's ring) with its routing recorded and, step by
    step, the router's inputs and scores and every layer's attention before
    W_o, and must return the tokens the served path returned. The reference
    is then teacher-forced from position 0 with those choices of experts:
    the logit gaps, the routing margins, the router in float32 on its own
    inputs, and each kind of layer's attention at the decode steps
    (`reference_mellum.teacher_forced_gaps`).

    `plants` (names of `reference_mellum.PLANTS`): the longest sample is
    judged again under each, for the record of what the check can see.

    A traced run (`scopes_path`) also writes the scope of every instruction
    of the engine's compiled steps (lib/scopes.py), at every chunk width."""
    import json

    from benchmark.lib import reference_mellum as ref
    from benchmark.lib import scopes

    engine = actor.engine
    if scopes_path:
        texts = await asyncio.to_thread(engine.step_hlo, [])
        with open(scopes_path, "w") as f:
            json.dump({program: scopes.instruction_scopes(hlos)
                       for program, hlos in texts.items()}, f)
    replays, took = [], []
    for s in samples:
        t0 = time.monotonic()
        replays.append(await engine.check_routing(
            s["prompt_ids"], max(2, len(s["answer_ids"])), mechanisms=True))
        took.append(time.monotonic() - t0)
    weights = ProgramWeightsMellum(
        engine.params, config["num_key_value_heads"] * config["head_dim"])
    longest = max(range(len(samples)),
                  key=lambda i: len(samples[i]["prompt_ids"]))

    def run():
        out = []
        for i, (s, r) in enumerate(zip(samples, replays)):
            t0 = time.monotonic()
            n, plen = len(s["answer_ids"]), len(s["prompt_ids"])
            args = (config, weights, s["prompt_ids"], s["answer_ids"],
                    r["routing"][:, : plen + max(n, 1) - 1], pad_multiple)
            attn_o = r.get("attn_o")
            g = ref.teacher_forced_gaps(*args, attn_o=attn_o)
            g["replay_equal"] = list(r["token_ids"][:n]) == list(
                s["answer_ids"])
            g["prompt_tokens"] = plen
            g.update(ref.router_readings(r, weights.routers()))
            if i == longest:
                g["plants"] = {
                    name: _planted(ref.teacher_forced_gaps(
                        *args, attn_o=attn_o, **ref.PLANTS[name]))
                    for name in plants}
            g["seconds"] = {"replay": took[i],
                            "reference": time.monotonic() - t0}
            out.append(g)
        return out

    return await asyncio.to_thread(run)


def _planted(g: Dict[str, Any]) -> Dict[str, Any]:
    """What a planted pass is judged by, and no more."""
    return {"gaps": g["gaps"], "max_abs_logit": g["max_abs_logit"],
            "expert_steps": g["routing"]["expert_steps"],
            "attn_window_error": g["attn_window_error"],
            "attn_full_error": g["attn_full_error"]}

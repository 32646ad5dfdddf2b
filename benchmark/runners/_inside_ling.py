"""What `_inside.py` is to the Llama block, for the Ling family: the one
place that knows the names `ray_tpu.models.ling` gives its parameters (the
reference, lib/reference_ling.py, knows its own), and the reference check
that runs where the weights are.
"""

from __future__ import annotations

import asyncio
from typing import Any, Dict, List

import numpy as np

# reference name -> program name, per kind of block
KDA_NAMES = {"wa": "wa", "wbeta": "wbeta", "wg": "wg", "A_log": "A_log",
             "dt_bias": "dt_bias", "o_norm": "o_norm", "wo": "wo"}
MLA_NAMES = {"wq": "wq", "wkva": "wkva", "kv_norm": "kv_norm", "wkvb": "wkvb",
             "wg": "wg", "wo": "wo"}
DENSE_NAMES = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
MOE_NAMES = {"router": "router", "router_bias": "router_bias",
             "sh_gate": "sh_w1", "sh_up": "sh_w3", "sh_down": "sh_w2"}
EXPERT_NAMES = {"w_gate": "e_w1", "w_up": "e_w3", "w_down": "e_w2"}


class ProgramWeightsLing:
    """The reference's view of the program's parameter tree: one layer at a
    time, cast to float32; the held experts a block at a time."""

    def __init__(self, params: Dict[str, Any]):
        self.params = params

    @staticmethod
    def _f32(x):
        import jax.numpy as jnp

        return x.astype(jnp.float32)

    def embed(self, tokens):
        return self._f32(self.params["tok_emb"][tokens])

    def layer(self, i: int) -> Dict[str, Any]:
        import jax.numpy as jnp

        p = self.params["layers"][i]
        out = {"attn_norm": self._f32(p["ln1"]), "ffn_norm": self._f32(p["ln2"])}
        if "wqkv" in p:
            names = KDA_NAMES
            # the program fuses q, k and v and their convolutions
            for ref, w in zip(("wq", "wk", "wv"), jnp.split(p["wqkv"], 3, -1)):
                out[ref] = self._f32(w)
            for ref, w in zip(("conv_q", "conv_k", "conv_v"),
                              jnp.split(p["conv"], 3, -1)):
                out[ref] = self._f32(w)
        else:
            names = MLA_NAMES
        names = {**names, **(MOE_NAMES if "router" in p else DENSE_NAMES)}
        out.update({ref: self._f32(p[prog]) for ref, prog in names.items()})
        return out

    def experts(self, i: int, lo: int, hi: int) -> Dict[str, Any]:
        p = self.params["layers"][i]
        return {ref: self._f32(p[prog][lo:hi])
                for ref, prog in EXPERT_NAMES.items()}

    def routers(self) -> List[Any]:
        """W_r of each expert layer, float32."""
        return [self._f32(p["router"]) for p in self.params["layers"]
                if "router" in p]

    def router_norms(self) -> List[np.ndarray]:
        """|W_r[:, e]| of each expert layer, for the routing margin's step."""
        return [np.linalg.norm(np.asarray(p["router"], np.float32), axis=0)
                for p in self.params["layers"] if "router" in p]

    def final_norm(self):
        return self._f32(self.params["norm"])

    def head(self):
        return self._f32(self.params["lm_head"])


async def engine_reference_check(actor, hp: Dict[str, Any],
                                 samples: List[Dict[str, Any]],
                                 pad_multiple: int, *, config: Dict[str, Any],
                                 scopes_path: str = None,
                                 prefill_tokens: List[int] = (),
                                 state_steps: int = 0
                                 ) -> List[Dict[str, Any]]:
    """`_inside.engine_reference_check` for a model with routed experts.
    Each sample's request is run once more through the engine's own loop
    (the timed path's programs, one request at a time as the check sent
    them) with its routing recorded: it must return the tokens the served
    path returned, so the routing is that of the answer being judged. Then
    the reference is teacher-forced with those choices. `config` is the
    configuration file (`hp` holds only its numbers).

    The replays also record what every decode step's router and recurrence
    computed from, and the slot's state before and after: the reference is
    given the same inputs and must arrive at the same scores and state
    (`reference_ling.mechanism_readings`), which holds the router and the
    state to float32 whatever the activations lose. The first sample's
    replay decodes `state_steps` tokens past the served answer, so that the
    state is compared after some hundreds of steps.

    A traced run (`scopes_path`) also writes, here in the set-up and not in
    the window, the scope of every instruction of the engine's compiled steps
    (lib/scopes.py): the trace's events carry an instruction's text, not
    where in the model it came from."""
    import json

    from benchmark.lib import reference_ling, scopes

    engine = actor.engine
    if scopes_path:
        texts = await asyncio.to_thread(engine.step_hlo, list(prefill_tokens))
        with open(scopes_path, "w") as f:
            json.dump({program: scopes.instruction_scopes(hlos)
                       for program, hlos in texts.items()}, f)
    replays = []
    for i, s in enumerate(samples):
        replays.append(await engine.check_routing(
            s["prompt_ids"], mechanisms=True,
            max_tokens=max(2, len(s["answer_ids"]))
            + (state_steps if i == 0 else 0)))
    weights = ProgramWeightsLing(engine.params)

    def run():
        out = []
        for s, r in zip(samples, replays):
            n = len(s["answer_ids"])
            g = reference_ling.teacher_forced_gaps(
                config, weights, s["prompt_ids"], s["answer_ids"],
                r["routing"][:, : len(s["prompt_ids"]) + max(n, 1) - 1],
                pad_multiple)
            g["replay_equal"] = list(r["token_ids"][:n]) == list(
                s["answer_ids"])
            g["mechanisms"] = reference_ling.mechanism_readings(
                r, weights.routers())
            out.append(g)
        return out

    return await asyncio.to_thread(run)


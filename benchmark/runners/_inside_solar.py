"""What `_inside.py` is to the Llama block, for the Solar-Open2 family: the
one place that knows the names `ray_tpu.models.solar` gives its parameters
(the reference, lib/reference_solar.py, knows its own), and the reference
check that runs where the weights are.
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Any, Dict, List

import numpy as np

# reference name -> program name, per kind of block
KDA_NAMES = {"wa_down": "wa_down", "wa_up": "wa_up", "wbeta": "wbeta",
             "wg_down": "wg_down", "wg_up": "wg_up", "A_log": "A_log",
             "dt_bias": "dt_bias", "o_norm": "o_norm", "wo": "wo"}
GQA_NAMES = {"wg": "wg", "wo": "wo"}
MOE_NAMES = {"router": "router", "router_bias": "router_bias",
             "sh_gate": "sh_w1", "sh_up": "sh_w3", "sh_down": "sh_w2"}
EXPERT_NAMES = {"w_gate": "e_w1", "w_up": "e_w3", "w_down": "e_w2"}


class ProgramWeightsSolar:
    """The reference's view of the program's parameter tree: one layer at a
    time, cast to float32; the held experts a block at a time. `kv_width`:
    kv_heads x head_dim, where the GQA layers' packed leaf is cut."""

    def __init__(self, params: Dict[str, Any], kv_width: int):
        self.params, self.kv_width = params, kv_width

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
        if "conv" in p:
            names = KDA_NAMES
            # the program fuses q, k and v and their convolutions
            for ref, w in zip(("wq", "wk", "wv"), jnp.split(p["wqkv"], 3, -1)):
                out[ref] = self._f32(w)
            for ref, w in zip(("conv_q", "conv_k", "conv_v"),
                              jnp.split(p["conv"], 3, -1)):
                out[ref] = self._f32(w)
        else:
            names = GQA_NAMES
            # the program packs [q | k | v] into one leaf
            nq = p["wqkv"].shape[1] - 2 * self.kv_width
            out["wq"] = self._f32(p["wqkv"][:, :nq])
            out["wk"] = self._f32(p["wqkv"][:, nq:nq + self.kv_width])
            out["wv"] = self._f32(p["wqkv"][:, nq + self.kv_width:])
        out.update({ref: self._f32(p[prog])
                    for ref, prog in {**names, **MOE_NAMES}.items()})
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
                                 state_steps: int = 0,
                                 second_readings: bool = False
                                 ) -> List[Dict[str, Any]]:
    """`_inside.engine_reference_check` for a model with routed experts and
    a recurrent state under a prefix cache. The samples are one session: a
    document's questions, of which all but the first were answered from a
    state snapshot. Each is run once more through the engine's own loop with
    its routing recorded and must return the tokens the served path
    returned: the first **cold**, from position 0 whatever is cached, which
    gives the program's routing at every position of the document; the
    others as served, resumed from a snapshot (the check fails if none
    did), which gives the routing of the positions they ran; the shared
    positions before take the first replay's rows. The reference is then
    teacher-forced from position 0 with those choices, so a state that a
    snapshot or a hand-over between chunks lost or staled shows in the
    logits.

    The second sample's replay also records what its chunks' and decode
    steps' recurrence and its decode steps' router computed from, and
    decodes `state_steps` tokens past the served answer: the reference is
    given the same inputs from the snapshot's state and must arrive at the
    same scores and state (`reference_solar.mechanism_readings`).

    `second_readings`: the first sample is judged twice more, with the
    reference's activations in float8 and with its recurrent state in bf16,
    for the limits' second readings (PERF.md section 6).

    A traced run (`scopes_path`) also writes the scope of every instruction
    of the engine's compiled steps (lib/scopes.py), at every chunk width."""
    import json

    import jax.numpy as jnp

    from benchmark.lib import reference_solar, scopes

    engine = actor.engine
    if scopes_path:
        texts = await asyncio.to_thread(engine.step_hlo, [])
        with open(scopes_path, "w") as f:
            json.dump({program: scopes.instruction_scopes(hlos)
                       for program, hlos in texts.items()}, f)
    served_resumed = engine.stats().get("snapshots_restored", 0)
    replays, took = [], []
    for i, s in enumerate(samples):
        t0 = time.monotonic()
        replays.append(await engine.check_routing(
            s["prompt_ids"], cold=(i == 0), mechanisms=(i == 1),
            max_tokens=max(2, len(s["answer_ids"]))
            + (state_steps if i == 1 else 0)))
        took.append(time.monotonic() - t0)
    weights = ProgramWeightsSolar(
        engine.params, config["num_key_value_heads"] * config["head_dim"])
    first = samples[0]["prompt_ids"]

    def run():
        out = []
        for i, (s, r) in enumerate(zip(samples, replays)):
            t0 = time.monotonic()
            n, plen = len(s["answer_ids"]), len(s["prompt_ids"])
            start = int(r["resume_from"])
            routing = r["routing"][:, : plen - start + max(n, 1) - 1]
            if start:
                # the positions before the snapshot are the first sample's
                assert len(os.path.commonprefix(
                    [first, s["prompt_ids"]])) >= start
                routing = np.concatenate(
                    [replays[0]["routing"][:, :start], routing], axis=1)
            g = reference_solar.teacher_forced_gaps(
                config, weights, s["prompt_ids"], s["answer_ids"], routing,
                pad_multiple)
            g["replay_equal"] = list(r["token_ids"][:n]) == list(
                s["answer_ids"])
            g["resume_from"], g["served_resumed"] = start, served_resumed
            if "state0" in r:
                g["mechanisms"] = reference_solar.mechanism_readings(
                    r, weights.routers())
            if i == 0 and second_readings:
                for key, kw in (("fp8_activations",
                                 {"activations": jnp.float8_e4m3fn}),
                                ("bf16_state", {"state_dtype": jnp.bfloat16})):
                    low = reference_solar.teacher_forced_gaps(
                        config, weights, s["prompt_ids"], s["answer_ids"],
                        routing, pad_multiple, **kw)
                    g[key] = {"gaps": low["gaps"],
                              "max_abs_logit": low["max_abs_logit"],
                              "expert_steps": low["routing"]["expert_steps"]}
            # what the check costs the set-up, by part
            g["seconds"] = {"replay": took[i],
                            "reference": time.monotonic() - t0}
            out.append(g)
        return out

    return await asyncio.to_thread(run)

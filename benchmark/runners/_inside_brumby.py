"""What `_inside.py` is to the Llama block, for the Brumby family: the one
place that knows the names `ray_tpu.models.brumby` gives its parameters and
the layout `ray_tpu.ops.power_retention` documents for its state (the
reference, lib/reference_brumby.py, knows neither), and the reference check
that runs where the weights are.
"""

from __future__ import annotations

import asyncio
import os
import time
from typing import Any, Dict, List

import numpy as np

# reference name -> program name (stacked on a leading layer axis there)
LAYER_NAMES = {"attn_norm": "ln1", "q_norm": "q_norm", "k_norm": "k_norm",
               "wg": "wg", "bg": "bg", "wo": "wo", "ffn_norm": "ln2",
               "w_gate": "w1", "w_up": "w3", "w_down": "w2"}


class ProgramWeightsBrumby:
    """The reference's view of the program's parameter tree: one layer at a
    time, cast to float32; the head a block of the vocabulary at a time.
    `kv_width`: kv_heads x head_dim, where the packed [q | k | v] leaf is
    cut."""

    def __init__(self, params: Dict[str, Any], kv_width: int):
        self.params, self.kv_width = params, kv_width

    @staticmethod
    def _f32(x):
        import jax.numpy as jnp

        return x.astype(jnp.float32)

    def embed(self, tokens):
        return self._f32(self.params["tok_emb"][tokens])

    def layer(self, i: int) -> Dict[str, Any]:
        p = {k: v[i] for k, v in self.params["layers"].items()}
        nq = p["wqkv"].shape[1] - 2 * self.kv_width
        out = {"wq": self._f32(p["wqkv"][:, :nq]),
               "wk": self._f32(p["wqkv"][:, nq:nq + self.kv_width]),
               "wv": self._f32(p["wqkv"][:, nq + self.kv_width:])}
        out.update({ref: self._f32(p[prog])
                    for ref, prog in LAYER_NAMES.items()})
        return out

    def gates(self) -> List[Any]:
        """(w_g, b_g) of each layer, float32."""
        lay = self.params["layers"]
        return [(self._f32(lay["wg"][i]), self._f32(lay["bg"][i]))
                for i in range(lay["wg"].shape[0])]

    def final_norm(self):
        return self._f32(self.params["norm"])

    def head(self, lo: int, hi: int):
        return self._f32(self.params["lm_head"][:, lo:hi])


def unpack(state: np.ndarray) -> np.ndarray:
    """A slot's state [layers, KV, W, hd] in the program's layout ->
    [layers, KV, hd, hd, hd], by the function the program documents it
    with."""
    from ray_tpu.ops import power_retention

    return power_retention.unpack_state(state)


async def engine_reference_check(actor, hp: Dict[str, Any],
                                 samples: List[Dict[str, Any]],
                                 pad_multiple: int, *, config: Dict[str, Any],
                                 scopes_path: str = None,
                                 state_steps: int = 0,
                                 second_readings: bool = False
                                 ) -> List[Dict[str, Any]]:
    """`_inside.engine_reference_check` for a model whose only memory is a
    recurrent state under a prefix cache. The samples are one task: a header's
    items, of which the first ran cold, the second found the header's blocks
    without a snapshot and left one, and the later ones were answered from
    it (the check fails if none was). Each is run once more through the
    engine's own loop: the first **cold**, from position 0 whatever is
    cached, which must return the tokens the served cold run returned; the
    others resumed from the snapshot (where they part from their served
    answers is reported: a replay finds its own item's blocks cached too and
    cuts its chunks elsewhere). The reference is fed prompt + served answer
    from position 0, so a state that a snapshot or a hand-over between chunks
    lost or staled shows in the logits.

    The first replay also records what every row of its prompt's chunks ran
    the recurrence and the gate on; the third sample's replay records the
    same of the rows it ran (the item's chunks behind the restored
    snapshot) and of its decode steps, and decodes `state_steps` tokens past
    the served answer. The reference is given the program's own k, v and
    gamma of the whole sequence, the header's from the first replay, and its
    direct sum from position 0 must be what the slot holds at the end: a
    state that came through the header's chunks, a snapshot, a restore, the
    item's chunks and the decode steps
    (`reference_brumby.mechanism_readings`).

    `second_readings`: the first sample is judged once more with the
    reference's activations in float8, for the logit limit's second reading
    (PERF.md section 6).

    A traced run (`scopes_path`) also writes the scope of every instruction
    of the engine's compiled steps (lib/scopes.py), at every chunk width."""
    import json

    import jax.numpy as jnp

    from benchmark.lib import reference_brumby, scopes

    engine = actor.engine
    if scopes_path:
        texts = await asyncio.to_thread(engine.step_hlo, [])
        with open(scopes_path, "w") as f:
            json.dump({program: scopes.instruction_scopes(hlos)
                       for program, hlos in texts.items()}, f)
    before = engine.stats()
    served_shared = before.get("snapshots_shared", 0)
    probed = min(2, len(samples) - 1)
    replays, took = [], []
    for i, s in enumerate(samples):
        t0 = time.monotonic()
        replays.append(await engine.check_routing(
            s["prompt_ids"], cold=(i == 0), mechanisms=(i in (0, probed)),
            max_tokens=max(2, len(s["answer_ids"]))
            + (state_steps if i == probed else 0)))
        took.append(time.monotonic() - t0)
    weights = ProgramWeightsBrumby(
        engine.params, config["num_key_value_heads"] * config["head_dim"])

    def run():
        out = []
        for i, (s, r) in enumerate(zip(samples, replays)):
            t0 = time.monotonic()
            n = len(s["answer_ids"])
            g = reference_brumby.teacher_forced_gaps(
                config, weights, s["prompt_ids"], s["answer_ids"],
                pad_multiple)
            g["replay_equal"] = list(r["token_ids"][:n]) == list(
                s["answer_ids"])
            # where a replay parted from the served answer (-1: nowhere)
            g["replay_parts_at"] = next(
                (j for j, (a, b) in enumerate(zip(r["token_ids"],
                                                  s["answer_ids"])) if a != b),
                -1)
            g["resume_from"] = int(r["resume_from"])
            g["served_shared"] = served_shared
            if i == probed and i > 0:
                start = int(r["resume_from"])
                # the rows before the snapshot are the cold replay's
                assert len(os.path.commonprefix(
                    [samples[0]["prompt_ids"], s["prompt_ids"]])) >= start
                head = reference_brumby.recurrence_inputs(replays[0]["chunks"])
                tail = reference_brumby.recurrence_inputs(r["chunks"], r)
                rows = {k: np.concatenate([head[k][:start], tail[k]])
                        for k in tail}
                g["mechanisms"] = reference_brumby.mechanism_readings(
                    rows, unpack(r["state"]), weights.gates(),
                    low_rows=tail["k"].shape[0])
                g["mechanisms"]["state_steps"] = int(np.asarray(r["k"]).shape[0])
            if i == 0 and second_readings:
                low = reference_brumby.teacher_forced_gaps(
                    config, weights, s["prompt_ids"], s["answer_ids"],
                    pad_multiple, activations=jnp.float8_e4m3fn)
                g["fp8_activations"] = {"gaps": low["gaps"],
                                        "max_abs_logit": low["max_abs_logit"]}
            g["seconds"] = {"replay": took[i],
                            "reference": time.monotonic() - t0}
            out.append(g)
        return out

    return await asyncio.to_thread(run)

"""Functions the runners send into the processes that hold the chips.

`actor.__rt_call__.remote(fn, ...)` runs `fn(actor_instance, ...)` inside an
actor, so the benchmark reaches the engine's weights and its device without
a change to the program. This module is the one place that knows the names
the program gives its parameters; the reference knows its own.
"""

from __future__ import annotations

import asyncio
import time
from typing import Any, Dict, List

# reference name -> ray_tpu.models.llama parameter name (stacked on a leading
# layer axis there)
LAYER_NAMES = {"attn_norm": "ln1", "wq": "wq", "wk": "wk", "wv": "wv",
               "wo": "wo", "ffn_norm": "ln2", "w_gate": "w1", "w_up": "w3",
               "w_down": "w2"}


class ProgramWeights:
    """The reference's view (see lib/reference.py) of the program's parameter
    tree: one layer at a time, cast to float32, gathered onto one device."""

    def __init__(self, params: Dict[str, Any], device=None):
        self.params, self.device = params, device

    def _f32(self, x):
        import jax
        import jax.numpy as jnp

        x = x.astype(jnp.float32)
        return x if self.device is None else jax.device_put(x, self.device)

    def embed(self, tokens):
        return self._f32(self.params["tok_emb"][tokens])

    def layer(self, i: int):
        return {ref: self._f32(self.params["layers"][prog][i])
                for ref, prog in LAYER_NAMES.items()}

    def final_norm(self):
        return self._f32(self.params["norm"])

    def head(self):
        return self._f32(self.params["lm_head"])


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    # no Python frames: the program's own annotations name the idle gaps
    # (`engine:*`, PR 24), and the tracer's start-up (`$sys setprofile`,
    # 0.03-0.08 s) and per-call cost fell inside every traced window
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


# --- inside the LLMEngine actor --------------------------------------------


async def engine_trace(actor, logdir: str, seconds: float) -> Dict[str, Any]:
    """Trace this process, the one that holds the chip, for `seconds`:
    captured from `t0` to `t1`, written by `t2` (this process's
    `time.monotonic()`, which on one host is the runner's too)."""
    import jax

    jax.profiler.start_trace(logdir, profiler_options=profile_options())
    t0 = time.monotonic()
    try:
        await asyncio.sleep(seconds)
    finally:
        t1 = time.monotonic()
        await asyncio.to_thread(jax.profiler.stop_trace)
    return {"logdir": logdir, "t0": t0, "t1": t1, "t2": time.monotonic()}


def engine_memory(actor) -> Dict[str, Any]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return {"memory_peak_bytes": max(peaks) if peaks else None}


async def engine_reference_check(actor, hp: Dict[str, Any],
                                 samples: List[Dict[str, Any]],
                                 pad_multiple: int) -> List[Dict[str, Any]]:
    """Teacher-forced reference logits for each (prompt ids, answer ids),
    computed where the weights are, off the actor's event loop."""
    from benchmark.lib import reference

    weights = ProgramWeights(actor.engine.params)

    def run():
        return [reference.teacher_forced_gaps(
            hp, weights, s["prompt_ids"], s["answer_ids"], pad_multiple)
            for s in samples]

    return await asyncio.to_thread(run)


def replica_engines(replica) -> list:
    """Inside the serve replica that hosts DPEngineGroup: its engine actors."""
    return list(replica._callable.engines)

"""The engine's own phases and the device's programs, both from the traced
run's `.xplane.pb`, for the per-layer metrics that read them.

What the engine writes (ray_tpu/llm/_engine.py, `PHASES`): one
`jax.profiler.TraceAnnotation` per phase of its loop, as host events named
`engine:<phase>` on the profiler's clock, the device trace's own. One turn
of the loop is

    engine:sweep, engine:admit* (prefix_match, prefill | suffix_prefill,
    sample_first inside), engine:step (upload, dispatch, device_wait
    inside), engine:emit

and running sequences get no token while a prompt is being admitted. What
the device writes: the `XLA Modules` line of each `/device:TPU:<n>` plane,
one event per execution of a program, named `jit_<function>(<fingerprint>)`;
the engine's are `jit_paged_decode_step`, `jit_paged_prefill` and
`jit_paged_suffix_prefill`.

A program without the annotations or the names (the parent of the PR that
added them) leaves nothing to find: `load` then holds empty lists, and the
readers return None.

Works on the plain-data trace of lib/xplane.py, so that it can be checked
against a small recorded trace (tests/test_engine_spans.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import xplane

Event = xplane.Event
MODULES_LINE = "XLA Modules"
ENGINE = "engine:"
SWEEP, ADMIT, DEVICE_WAIT = "engine:sweep", "engine:admit", "engine:device_wait"
DECODE_MODULE = "jit_paged_decode_step"
PREFILL_MODULES = ("jit_paged_prefill", "jit_paged_suffix_prefill")
NS = 1e-9


def engine_events(trace: xplane.Trace) -> List[Event]:
    """Every `engine:*` host event, whatever thread wrote it, by start."""
    return sorted(((name, s, d)
                   for events in trace.get(xplane.HOST_PLANE, {}).values()
                   for name, s, d in events if name.startswith(ENGINE)),
                  key=lambda e: e[1])


def reduce(trace: xplane.Trace, window_s: Optional[float] = None) -> dict:
    """`loop`: the engine's events over whole turns of its loop, from the
    first `engine:sweep` that starts inside the window to the last one, so
    that every admission and every device wait counted is a whole one and
    their complement is host time and nothing else. `modules`: per chip, the
    program executions that overlap the window, uncut. The window is the one
    `xplane.reduce` used: the last `window_s` seconds up to the last device
    operation (it leaves out the head of the trace); a trace without a
    device plane (the CPU rehearsal) is taken whole."""
    planes = xplane.device_planes(trace)
    events = engine_events(trace)
    if planes:
        lo, hi = xplane.window_of(trace)
        if window_s is not None:
            lo = max(lo, hi - window_s / NS)
    elif events:
        lo, hi = events[0][1], max(s + d for _, s, d in events)
    else:
        lo = hi = 0.0
    sweeps = [s for name, s, _ in events if name == SWEEP and lo <= s <= hi]
    loop: List[Event] = []
    if len(sweeps) >= 2:
        loop = [e for e in events if sweeps[0] <= e[1] < sweeps[-1]]
    modules = {p: [(name, s, d)
                   for name, s, d in trace[p].get(MODULES_LINE, [])
                   if s < hi and s + d > lo]
               for p in planes}
    return {"window": (lo, hi), "loop": loop, "modules": modules,
            "loop_wall_s": (sweeps[-1] - sweeps[0]) * NS if loop else 0.0}


def load(art: Dict[str, Any]) -> Optional[dict]:
    """`reduce` of the run's trace, read once and kept on `art`; None for a
    run that was not traced."""
    if "host_spans" not in art:
        call = art.get("trace_call")
        path = xplane.find_xplane(call["logdir"]) if call else None
        art["host_spans"] = None if path is None else reduce(
            xplane.load(path), (art.get("trace") or {}).get("window_s"))
    return art["host_spans"]


def total_s(events: List[Event], name: str) -> Tuple[float, int]:
    """Summed duration (seconds) and number of the events called `name`."""
    durations = [d for n, _, d in events if n == name]
    return sum(durations) * NS, len(durations)


def module_events(reduced: dict, prefixes: Tuple[str, ...]
                  ) -> List[List[Event]]:
    """Per chip, the executions of the programs whose name starts with one
    of `prefixes` followed by the fingerprint's bracket."""
    starts = tuple(p + "(" for p in prefixes)
    return [[e for e in events if e[0].startswith(starts)]
            for events in reduced["modules"].values()]

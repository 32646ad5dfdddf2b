"""Device time by the part of the model it was spent in.

The program wraps its three mechanisms in `jax.named_scope("kda")`, `("mla")`
and `("moe")`. A scope reaches the compiled program as a component of each
instruction's `op_name` metadata (`jit(paged_decode_step)/kda/mul`); the
device trace's events (`XLA Ops`) carry an instruction's HLO text without it.
So the engine writes its compiled steps' HLO beside the trace
(`PagedEngine.step_hlo`, through runners/_inside_ling.py), `instruction_scopes`
reduces each program's text to {instruction label: scope}, and `scope_times`
sums the trace's events by scope: an event belongs to the program execution
(`XLA Modules`) it lies in, and is looked up under that program's name by its
label, `xplane.short_name`: the label `breakdown.device_ops` uses. A fusion
takes the scope of its root instruction's metadata, as XLA records it. A
`conditional` or `while` operation lies on the line around the operations of
its body: each operation is counted for the time no operation inside it
covers, so nothing is counted twice. What no scope claims (norms, embeddings, head, sampling, dense layers, programs other
than the engine's steps) is `rest`; the four sum to the busy time.

XLA's TPU rewrite of `ragged_dot` into a grouped-matmul custom call drops the
`op_name` (`ragged-dot-none`); only the expert layers call it, so those are
`moe` by name.
"""

from __future__ import annotations

import json
import re
from typing import Any, Dict, List, Optional

from benchmark.lib import host_spans, xplane

SCOPES = ("kda", "mla", "moe")
REST = "rest"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%\S+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def scope_of(label: str, op_name: Optional[str]) -> Optional[str]:
    if label.startswith("ragged-dot"):
        return "moe"
    if not op_name:
        return None
    parts = op_name.split("/")
    return next((p for p in parts if p in SCOPES), None)


def instruction_scopes(hlo_texts: List[str]) -> Dict[str, str]:
    """{label: scope} over the instructions of one program's compilations
    (a prefill compiles once a bucket). A label that two of them give to
    instructions of different scopes is dropped: it counts as `rest`."""
    found: Dict[str, Optional[str]] = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTRUCTION.match(line)
            if not m:
                continue
            label = xplane.short_name(m.group(1))
            op = _OP_NAME.search(line)
            scope = scope_of(label, op.group(1) if op else None) or REST
            if found.setdefault(label, scope) != scope:
                found[label] = None
    return {k: v for k, v in found.items() if v in SCOPES}


def scope_times(trace: xplane.Trace, scopes: Dict[str, Dict[str, str]],
                lo: float, hi: float) -> Dict[str, float]:
    """Seconds of device time per scope (and `rest`) inside [lo, hi),
    averaged over the chips."""
    planes = xplane.device_planes(trace)
    total = {s: 0.0 for s in SCOPES + (REST,)}
    for p in planes:
        modules = sorted(
            (s, s + d, name.split("(")[0])
            for name, s, d in trace[p].get(host_spans.MODULES_LINE, []))
        ops = sorted(xplane._clip(trace[p].get(xplane.OPS_LINE, []), lo, hi),
                     key=lambda e: e[1])
        ops.sort(key=lambda e: (e[1], -e[2]))
        i = 0
        around: List = []                  # (end, scope) of the enclosing ops
        for name, s, d in ops:
            while i < len(modules) and modules[i][1] <= s:
                i += 1
            program = (modules[i][2] if i < len(modules)
                       and modules[i][0] <= s else None)
            scope = scopes.get(program, {}).get(xplane.short_name(name), REST)
            while around and around[-1][0] <= s:
                around.pop()
            if around:
                total[around[-1][1]] -= d  # the body's time is the body's
            total[scope] += d
            around.append((s + d, scope))
    return {k: v * host_spans.NS / max(1, len(planes)) for k, v in total.items()}


def load(art: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """`scope_times` of the run's trace over the window `xplane.reduce` used,
    read once and kept on `art`; None where the run was not traced or the
    program wrote no scopes (a program without them)."""
    if "scope_times" not in art:
        art["scope_times"] = None
        spans, path = host_spans.load(art), art.get("scopes_path")
        call = art.get("trace_call")
        xp = xplane.find_xplane(call["logdir"]) if call else None
        try:
            with open(path) as f:
                scopes = json.load(f)
        except (OSError, TypeError, ValueError):
            scopes = None
        if spans and scopes and xp and xplane.device_planes(
                trace := xplane.load(xp)):
            art["scope_times"] = scope_times(trace, scopes, *spans["window"])
    return art["scope_times"]


def share(art: Dict[str, Any], scope: str) -> Optional[float]:
    """A scope's device time as a share of the busy time, %."""
    times = load(art)
    busy = (art.get("trace") or {}).get("busy_s")
    if not times or not busy:
        return None
    return 100.0 * times[scope] / busy

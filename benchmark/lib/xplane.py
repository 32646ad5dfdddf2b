"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the numbers the
per-layer metrics read: device busy and idle time, time per operation,
collective time that no compute hides, the Pallas kernels, and the longest
idle gaps named by what the host was doing.

What a v5e trace holds (looked at by hand, PR 22): one plane per chip,
`/device:TPU:<n>`, with the lines `XLA Ops` (what the core executes, one
operation after the other), `Async XLA Ops` (the lifetime of asynchronous
copies and collectives), `XLA Modules` and `Steps`; and `/host:CPU` with one
line per host thread (`python`, runtime threads). An operation's name is its
HLO text: `%fusion.3 = bf16[..] fusion(...)`, and a Pallas kernel is a
`custom-call` whose text says `custom_call_target="tpu_custom_call"`. All
times are nanoseconds on one clock.

Everything below the loader works on plain data so that it can be checked
against a small recorded trace (tests/test_xplane.py):

    trace = {plane_name: {line_name: [(event_name, start_ns, duration_ns), ...]}}
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Event = Tuple[str, float, float]
Trace = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'
# host frames that say nothing about what the host was doing
_HOST_NOISE = ("$threading.py", "$<frozen", "$asyncio/", "$selectors.py",
               "$concurrent/futures", "$base_events.py", "$events.py",
               "$runners.py", "$thread.py")


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")), key=os.path.getmtime)
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    """Read an `.xplane.pb` with nothing but JAX."""
    from jax.profiler import ProfileData

    trace: Trace = {}
    for plane in ProfileData.from_file(path).planes:
        lines = trace.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return trace


def device_planes(trace: Trace) -> List[str]:
    return sorted((p for p in trace if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def opcode(name: str) -> str:
    """`%x = bf16[2,3]{1,0} fusion(...)` -> `fusion`. A name that is not
    HLO text is returned whole."""
    m = re.search(r"=\s*(?:\([^=]*?\)|\S+)\s+([a-z][a-z0-9\-]*)\(", name)
    return m.group(1) if m else name


def short_name(name: str) -> str:
    """`%fusion.3 = bf16[8,128]{1,0} fusion(...)` -> `fusion.3 bf16[8,128]`:
    the label used in breakdowns (a tuple result's shapes are left out)."""
    m = re.match(r"%(\S+)\s*=\s*(\w+\[[\d,]*\])?", name)
    label = m.group(1) + (" " + m.group(2) if m.group(2) else "") if m else name
    if PALLAS_TARGET in name:
        label += " [pallas]"
    return label[:80]


def is_collective(name: str) -> bool:
    op = opcode(name)
    return any(op == c or op == c + "-start" or op == c + "-done"
               for c in COLLECTIVES)


def is_pallas(name: str) -> bool:
    return PALLAS_TARGET in name


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted [start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(events: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def window_of(trace: Trace) -> Tuple[float, float]:
    """The span from the first to the last device operation: the traced
    window as the devices saw it."""
    starts, ends = [], []
    for p in device_planes(trace):
        ev = trace[p].get(OPS_LINE, [])
        if ev:
            starts.append(min(s for _, s, _ in ev))
            ends.append(max(s + d for _, s, d in ev))
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


# The device's and the host's clocks in one trace differ by about a
# millisecond (PR 22's probe: a device gap ends 0.85 ms before the host's
# sleep that caused it), so a gap much shorter than that cannot be laid at a
# host event's door.
SHORT_GAP_NS = 100_000.0
SHORT_GAPS = "gaps under 0.1 ms"


def _host_events(trace: Trace) -> List[Event]:
    """Host events that can name a gap: long enough to cover half of the
    shortest gap that gets a name, framework plumbing left out."""
    return [(name, s, d)
            for events in trace.get(HOST_PLANE, {}).values()
            for name, s, d in events
            if d >= 0.5 * SHORT_GAP_NS and not name.startswith(_HOST_NOISE)]


def _host_label(host: List[Event], lo: float, hi: float) -> str:
    """What the host was doing during [lo, hi): the shortest host event
    that covers at least half of it."""
    if hi - lo < SHORT_GAP_NS:
        return SHORT_GAPS
    best, best_d = "unattributed", float("inf")
    for name, s, d in host:
        if d < best_d and min(s + d, hi) - max(s, lo) >= 0.5 * (hi - lo):
            best, best_d = name, d
    return best[:80]


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           top: int = 10, skip_head_s: float = 0.0) -> dict:
    """Busy/idle, per-operation time, collectives and idle gaps.

    Per chip, busy is the union of the intervals in which an operation ran
    on the core (`XLA Ops`); a collective on that line keeps the core from
    computing for as long as it runs there, so its time is exposed time.
    Numbers are averaged over the chips; gaps and operations are summed by
    name over all chips and divided by their number. `skip_head_s` leaves
    out the start of the trace, where the profiler's own start-up (installing
    the Python tracer, `$sys setprofile`) stalls the host for 0.1-0.25 s.
    """
    planes = device_planes(trace)
    if not planes:
        raise ValueError("the trace holds no /device:TPU plane")
    lo, hi = window or window_of(trace)
    lo = min(lo + skip_head_s * 1e9, hi)
    n = len(planes)
    host = _host_events(trace)
    busy = exposed = collective_total = pallas = 0.0
    per_op: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    pallas_events: List[Event] = []
    for p in planes:
        ops = _clip(trace[p].get(OPS_LINE, []), lo, hi)
        merged = union((s, s + d) for _, s, d in ops)
        busy += sum(e - s for s, e in merged)
        for name, s, d in ops:
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + d
            if is_collective(name):
                exposed += d
            if is_pallas(name):
                pallas += d
                pallas_events.append((name, s, d))
        # asynchronous collectives live on their own line from start to done
        for name, s, d in _clip(trace[p].get("Async XLA Ops", []), lo, hi):
            if is_collective(name):
                collective_total += d
        collective_total += sum(d for name, _, d in ops if is_collective(name)
                                and not opcode(name).endswith(("-start", "-done")))
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = _host_label(host, a, b)
                gaps[label] = gaps.get(label, 0.0) + (b - a)
    ns = 1e-9

    def ranked(d):
        return [[k, v * ns / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "chips": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n,
        "idle_share": 1.0 - busy / (n * (hi - lo)),
        "collective_exposed_s": exposed * ns / n,
        "collective_total_s": collective_total * ns / n,
        "pallas_s": pallas * ns / n,
        "pallas_events": pallas_events,
        "device_ops": ranked(per_op),
        "idle_gaps": ranked(gaps),
    }


# --- Pallas flash-attention calls, told apart by their signature ------------

_SHAPE = re.compile(r"(bf16|f16|f32)\[([\d,]+)\]")


def _shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(t, tuple(int(x) for x in dims.split(",")))
            for t, dims in _SHAPE.findall(text)]


def flash_call_shape(name: str) -> Optional[dict]:
    """Recognise one of ops/flash_attention.py's kernels from the HLO text
    of its custom call, since the program gives its kernels no names:

      fwd  (q, k, v)                    -> (o, lse)
      dq   (q, k, v, do, lse, delta)    -> dq   [b, h, sq, hd]
      dkv  (q, k, v, do, lse, delta)    -> (dk, dv), one pair per query head

    with q [b, h, sq, hd] and k, v [b, kvh, sk, hd] always the first three
    operands.

    Returns the arguments of `flops.flash_kernel_cost`, or None for a call
    that does not look like any of them (ring/chunk kernels, other Pallas
    code)."""
    if not is_pallas(name):
        return None
    m = re.search(r"=\s*(.*?)\s+custom-call\((.*?)\),\s*custom_call_target",
                  name, re.S)
    if not m:
        return None
    outs, ins = _shapes(m.group(1)), _shapes(m.group(2))
    if len(ins) < 3 or any(len(s) != 4 for _, s in ins[:3]):
        return None
    q, k, v = (s for _, s in ins[:3])
    if not (q[-1] == k[-1] == v[-1] and k == v and q[0] == k[0]):
        return None
    kind = {(3, 2): "fwd", (6, 1): "dq", (6, 2): "dkv"}.get((len(ins), len(outs)))
    if kind is None or (kind == "dq" and outs[0][1] != q):
        return None
    itemsize = 2 if ins[0][0] in ("bf16", "f16") else 4
    return {"kind": kind, "batch": q[0], "heads": q[1], "kv_heads": k[1],
            "sq": q[2], "sk": k[2], "hd": q[3], "itemsize": itemsize}

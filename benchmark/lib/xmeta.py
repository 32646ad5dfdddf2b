"""What a device trace says of each operation beside its name and its time,
and the train step read by it: by pass, by scope and against the peak.

`jax.profiler.ProfileData` (lib/xplane.py's loader) gives an event's name,
start, duration and the *event's* stats. The `.xplane.pb` also holds, for
every entry of a device plane's `XLA Ops` line, the *event metadata's* stats,
which `ProfileData` does not expose (read by hand in PR 42's trace):

    tf_op           the instruction's `op_name`, JAX's name stack and all:
                    `jit(train_step)/transpose(jvp(layers))/while/body/
                    closed_call/checkpoint/attn/dot_general:`
    hlo_category    `convolution fusion` (a matmul), `loop fusion`,
                    `custom-call`, `all-gather`, ...
    model_flops     operations of one execution, as XLA counts them
    bytes_accessed  XLA's estimate, not HBM traffic (loop fusions sum to
                    more than the chip's bandwidth): no roofline is read
                    from it
    program_id      the fingerprint in the `XLA Modules` name

`load` decodes them with the standard library alone (the chip's machine has
no protobuf module for this file; tests/test_xmeta.py holds every field
number against `xplane_pb2` where that imports), following tsl's
`xplane.proto`:

    XSpace          planes 1
    XPlane          name 2, lines 3, event_metadata 4, stat_metadata 5
                    (maps: an entry's key 1, value 2)
    XLine           name 2, timestamp_ns 3, events 4
    XEvent          metadata_id 1, offset_ps 2, duration_ps 3, stats 4
    XEventMetadata  id 1, name 2, display_name 4, stats 5
    XStat           metadata_id 1, double 2, uint64 3, int64 4, str 5,
                    bytes 6, ref 7 (a stat_metadata id whose name is the value)
    XStatMetadata   id 1, name 2

Everything below the loader works on plain data,

    planes = {"/device:TPU:0": {"XLA Ops": [(start_ns, duration_ns, meta)],
                                "XLA Modules": [...]}}

with `meta` one dict an instruction (`META_KEYS`; a key the file lacks is
None), shared by its events, so that it can be checked against a small trace
written by hand.

**The train step's table.** `models.llama.TRAIN_SCOPES` names the step's
parts with `jax.named_scope`; a scope is a component of `tf_op`. Under a
transform JAX wraps the first scope's name (`transpose(jvp(layers))/../attn`),
so a `tf_op` is split on `/`, `(` and `)`. An operation's scope is the first
of embed / layers / loss / optimizer among its tokens (`REST` without one,
`NO_TF_OP` without a `tf_op`); its pass is `remat` if `rematted_computation`
is a token, else `bwd` under a `transpose(` (the transform: the primitive of
that name ends a `tf_op` and opens no bracket), else `fwd`. Time is
exclusive by lib/scopes.py's rule: a `while` or `conditional` lies on the
line around its body's operations and counts only what none of them covers,
so the table's cells sum to the busy time. The window is the one
`xplane.reduce` used; numbers are averaged over the chips.
"""

from __future__ import annotations

import re
import struct
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmark.lib import host_spans, xplane

Op = Tuple[float, float, Dict[str, Any]]
Planes = Dict[str, Dict[str, List[Op]]]

LINES = (xplane.OPS_LINE, host_spans.MODULES_LINE)
META_KEYS = ("name", "tf_op", "hlo_category", "model_flops", "bytes_accessed",
             "program_id")
TOP_SCOPES = ("embed", "layers", "loss", "optimizer")
INNER_SCOPES = ("attn", "mlp")          # only inside `layers`
REST, NO_TF_OP = "rest", "no tf_op"
PASSES = ("fwd", "bwd", "remat")
MATMUL_CATEGORIES = ("convolution fusion", "convolution")
TRAIN_MODULE = "jit_train_step"

# --- the wire format ---------------------------------------------------------

_VARINT, _FIXED64, _BYTES, _FIXED32 = 0, 1, 2, 5


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _signed(v: int) -> int:
    """An int64 field's value: a negative one is sent as its two's
    complement in 64 bits."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of the message in buf[lo:hi]: an
    int for a varint, the 8 or 4 raw bytes of a fixed field, (lo, hi) of a
    length-delimited one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
        elif wire == _BYTES:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == _FIXED64:
            value = buf[i:i + 8]
            i += 8
        elif wire == _FIXED32:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an xplane.pb")
        yield number, wire, value
    if i != hi:
        raise ValueError("a message runs past its length: not an xplane.pb")


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stat(buf: bytes, span, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """(name, value) of one XStat; no value for a name `META_KEYS` lacks
    (the name is sent first: a `source_stack` is never decoded)."""
    name, value = "", None
    for number, _, v in _fields(buf, *span):
        if number == 1:
            name = stat_names.get(v, str(v))
            if name not in META_KEYS:
                break
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _signed(v)
        elif number == 5:
            value = _text(buf, v)
        elif number == 6:
            value = buf[v[0]:v[1]]
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_values(buf: bytes, spans) -> Iterator[Tuple[int, int]]:
    """The value message of each entry of a map field."""
    for span in spans:
        for number, _, v in _fields(buf, *span):
            if number == 2:
                yield v


def _plane(buf: bytes, span) -> Tuple[str, Dict[str, List[Op]]]:
    name, lines, event_md, stat_md = "", [], [], []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(v)
        elif number == 5:
            stat_md.append(v)
    if not xplane.DEVICE_PLANE.match(name):
        return name, {}
    stat_names: Dict[int, str] = {}
    for value in _map_values(buf, stat_md):
        f = {n: v for n, _, v in _fields(buf, *value)}
        stat_names[f.get(1, 0)] = _text(buf, f[2]) if 2 in f else ""
    metas: Dict[int, Dict[str, Any]] = {}
    for value in _map_values(buf, event_md):
        meta: Dict[str, Any] = dict.fromkeys(META_KEYS)
        key = 0
        for number, _, v in _fields(buf, *value):
            if number == 1:
                key = _signed(v)
            elif number == 2:
                meta["name"] = _text(buf, v)
            elif number == 5:
                stat, x = _stat(buf, v, stat_names)
                if stat in meta and stat != "name":   # `name` is the field
                    meta[stat] = x
        metas[key] = meta
    out: Dict[str, List[Op]] = {}
    for span in lines:
        line_name, t0, events = "", 0, []
        for number, _, v in _fields(buf, *span):
            if number == 2:
                line_name = _text(buf, v)
            elif number == 3:
                t0 = _signed(v)
            elif number == 4:
                events.append(v)
        if line_name not in LINES:
            continue
        ops = out.setdefault(line_name, [])
        for ev in events:
            mid = offset = duration = 0
            for number, wire, v in _fields(buf, *ev):
                if wire == _VARINT:
                    if number == 1:
                        mid = v
                    elif number == 2:
                        offset = v
                    elif number == 3:
                        duration = v
            ops.append((t0 + _signed(offset) / 1e3, _signed(duration) / 1e3,
                        metas.get(_signed(mid)) or dict.fromkeys(META_KEYS)))
    return name, out


def load(path: str) -> Planes:
    """The device planes of an `.xplane.pb`: their `XLA Ops` and `XLA Modules`
    events in file order, times in nanoseconds as `xplane.load` gives them
    (line timestamp + offset), each with its instruction's metadata."""
    with open(path, "rb") as f:
        buf = f.read()
    planes: Planes = {}
    for number, _, v in _fields(buf, 0, len(buf)):
        if number == 1:
            name, lines = _plane(buf, v)
            if lines:
                planes[name] = lines
    return planes


# --- the train step by scope and pass ----------------------------------------

_SPLIT = re.compile(r"[/()]")


def tokens(tf_op: str) -> List[str]:
    """`jit(train_step)/transpose(jvp(layers))/while/body/attn/dot_general:`
    -> jit, train_step, transpose, jvp, layers, while, body, attn,
    dot_general (the `:<type>` XLA appends is left out)."""
    return [t for t in _SPLIT.split(tf_op.rsplit(":", 1)[0]) if t]


def classify(meta: Dict[str, Any]) -> Tuple[str, Optional[str], str]:
    """(scope, attn | mlp | None inside layers, pass) of an instruction."""
    tf_op = meta.get("tf_op")
    if not tf_op:
        return NO_TF_OP, None, "fwd"
    toks = tokens(tf_op)
    scope = next((t for t in toks if t in TOP_SCOPES), REST)
    inner = next((t for t in toks if t in INNER_SCOPES), None) \
        if scope == "layers" else None
    if "rematted_computation" in toks:
        return scope, inner, "remat"
    return scope, inner, "bwd" if "transpose(" in tf_op else "fwd"


def exclusive(ops: List[Op], lo: float, hi: float
              ) -> List[Tuple[float, float, Dict]]:
    """(nanoseconds, share inside the window, meta) of each operation that
    reaches into [lo, hi): its time there less what the operations inside it
    cover (a `while` around its body's), and how much of it the window
    holds (1 but for an operation the window's edge cuts)."""
    clipped = []
    for s, d, meta in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            clipped.append((a, b - a, (b - a) / d, meta))
    clipped.sort(key=lambda e: (e[0], -e[1]))
    out: List[List] = []
    around: List[Tuple[float, int]] = []     # (end, index in out) enclosing
    for s, d, inside, meta in clipped:
        while around and around[-1][0] <= s:
            around.pop()
        if around:
            out[around[-1][1]][0] -= d       # the body's time is the body's
        around.append((s + d, len(out)))
        out.append([d, inside, meta])
    return [tuple(e) for e in out]


def reduce(planes: Planes, lo: float, hi: float) -> Optional[dict]:
    """The table over [lo, hi), averaged over the chips: `cells`
    {(scope, inner, pass): {"s", "matmul_s", "matmul_flops"}} (seconds,
    operations), their totals, whether any operation carried one of
    `TOP_SCOPES` (`scoped`), the mean execution of `jit_train_step` among
    those that start inside the window (`step_device_s`) and how many
    executions the window holds (`steps`, by overlap). None without a device
    plane."""
    names = sorted(p for p in planes if xplane.DEVICE_PLANE.match(p))
    if not names:
        return None
    n = len(names)
    cells: Dict[Tuple[str, Optional[str], str], Dict[str, float]] = {}
    scoped = False
    steps, whole = 0.0, []
    for p in names:
        for d, inside, meta in exclusive(
                planes[p].get(xplane.OPS_LINE, []), lo, hi):
            key = classify(meta)
            scoped = scoped or key[0] in TOP_SCOPES
            cell = cells.setdefault(
                key, {"s": 0.0, "matmul_s": 0.0, "matmul_flops": 0.0})
            cell["s"] += d * host_spans.NS / n
            if meta.get("hlo_category") in MATMUL_CATEGORIES:
                cell["matmul_s"] += d * host_spans.NS / n
                cell["matmul_flops"] += (
                    inside * float(meta.get("model_flops") or 0) / n)
        for s, d, meta in planes[p].get(host_spans.MODULES_LINE, []):
            if not (meta.get("name") or "").startswith(TRAIN_MODULE + "("):
                continue
            steps += max(0.0, min(s + d, hi) - max(s, lo)) / d / n if d else 0.0
            # the train loop stops the profiler between two steps: no
            # execution is cut by the trace's end, only by the window's start
            if s >= lo:
                whole.append(d)
    total = {k: sum(c[k] for c in cells.values())
             for k in ("s", "matmul_s", "matmul_flops")}
    return {"chips": n, "cells": cells, "scoped": scoped, "steps": steps,
            "step_device_s": (sum(whole) / len(whole) * host_spans.NS
                              if whole else None),
            "busy_s": total["s"], "matmul_s": total["matmul_s"],
            "matmul_flops": total["matmul_flops"]}


def seconds(r: dict, scope: Optional[str] = None, passes=PASSES) -> float:
    """Σ of the table's cells of `scope` (any, for None) in `passes`."""
    return sum(c["s"] for (s, _, p), c in r["cells"].items()
               if (scope is None or s == scope) and p in passes)


def table_lines(r: dict) -> List[str]:
    """`train scopes:` scope x pass -> ms a step, share of busy, the
    matmuls' share of the row and their TFLOP/s; attn / mlp inside layers,
    `rest` and `no tf_op` on their own lines."""
    per_step = 1e3 / r["steps"] if r["steps"] else float("nan")
    rows: Dict[Tuple[str, str], Dict[str, float]] = {}
    for (scope, inner, p), c in r["cells"].items():
        for label in {scope, f"{scope}/{inner}" if inner else scope}:
            row = rows.setdefault((label, p), dict.fromkeys(c, 0.0))
            for k, v in c.items():
                row[k] += v
    order = {s: i for i, s in enumerate(
        ("embed", "layers", "layers/attn", "layers/mlp", "loss", "optimizer",
         REST, NO_TF_OP))}
    out = [f"train scopes: {r['steps']:.2f} steps of "
           f"{(r['step_device_s'] or 0) * 1e3:.2f} ms on {r['chips']} chips, "
           f"busy {r['busy_s'] * per_step:.2f} ms a step"]
    for (label, p), row in sorted(
            rows.items(), key=lambda kv: (order.get(kv[0][0], 99),
                                          PASSES.index(kv[0][1]))):
        tf = (f", matmuls {100 * row['matmul_s'] / row['s']:.1f}% of it at "
              f"{row['matmul_flops'] / row['matmul_s'] / 1e12:.1f} TFLOP/s"
              if row["matmul_s"] else "")
        out.append(f"train scopes: {label:12s} {p:5s} "
                   f"{row['s'] * per_step:8.2f} ms a step "
                   f"{100 * row['s'] / r['busy_s']:6.2f}% of busy{tf}")
    return out


# --- for the readers -----------------------------------------------------------


def load_art(art: Dict[str, Any]) -> Optional[dict]:
    """`reduce` of the run's trace over the window `xplane.reduce` used, read
    once, logged once (`train scopes:` lines on stderr) and kept on `art`;
    None for a run that was not traced or whose trace holds no device plane
    (the CPU rehearsal)."""
    if "xmeta" not in art:
        art["xmeta"] = None
        call = art.get("trace_call")
        path = xplane.find_xplane(call["logdir"]) if call else None
        window_s = (art.get("trace") or {}).get("window_s")
        if path and window_s:
            t0 = time.monotonic()
            planes = load(path)
            ends = [s + d for lines in planes.values()
                    for s, d, _ in lines.get(xplane.OPS_LINE, [])]
            if ends:
                hi = max(ends)
                art["xmeta"] = r = reduce(planes, hi - window_s / host_spans.NS, hi)
                print(f"[bench] xmeta: read {path} in "
                      f"{time.monotonic() - t0:.1f} s", file=sys.stderr)
                for line in table_lines(r):
                    print(f"[bench] {line}", file=sys.stderr, flush=True)
    return art["xmeta"]


def share(art: Dict[str, Any], scope: Optional[str], passes=PASSES
          ) -> Optional[float]:
    """A scope's exclusive device time in `passes` as a share of the busy
    time, %. A scope's share (not `None`'s, which needs no name) is None on
    a program that wrote no scope: the parent of the PR that named them."""
    r = load_art(art)
    busy = (art.get("trace") or {}).get("busy_s")
    if not r or not busy or (scope is not None and not r["scoped"]):
        return None
    return 100.0 * seconds(r, scope, passes) / busy

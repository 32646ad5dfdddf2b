"""Device time under a `jax.named_scope` that lib/scopes.py's fixed tuple
does not know (`gqa`), read from the trace alone: every `XLA Ops` event's
metadata carries `tf_op`, the instruction's `op_name` with every scope in it
(lib/xmeta.py decodes it), so no compiled text is joined. Time is exclusive by
the rule both libraries share: a `while` or `conditional` lies around its
body's operations and counts only what none of them covers. The Pallas call
of the decode rows' attention keeps its `tf_op`, so the kernel counts.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from benchmark.lib import host_spans, xmeta, xplane


def scope_seconds(planes: xmeta.Planes, scope: str, lo: float, hi: float
                  ) -> Optional[float]:
    """Seconds of device time in [lo, hi) under `scope`, averaged over the
    chips; None without a device plane."""
    names = sorted(p for p in planes if xplane.DEVICE_PLANE.match(p))
    if not names:
        return None
    total = 0.0
    for p in names:
        for ns, _, meta in xmeta.exclusive(
                planes[p].get(xplane.OPS_LINE, []), lo, hi):
            if scope in xmeta.tokens(meta.get("tf_op") or ""):
                total += ns
    return total * host_spans.NS / len(names)


def share(art: Dict[str, Any], scope: str) -> Optional[float]:
    """A scope's device time as a share of the busy time, %, over the window
    `xplane.reduce` used (as lib/scopes.py's shares are); None for a run
    that was not traced, or whose program wrote no such scope."""
    key = f"scope_seconds.{scope}"
    if key not in art:
        art[key] = None
        spans, call = host_spans.load(art), art.get("trace_call")
        path = xplane.find_xplane(call["logdir"]) if call else None
        if spans and path:
            art[key] = scope_seconds(xmeta.load(path), scope, *spans["window"])
    busy = (art.get("trace") or {}).get("busy_s")
    if not art[key] or not busy:
        return None
    return 100.0 * art[key] / busy

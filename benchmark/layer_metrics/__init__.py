"""Per-layer metrics: one small reader per file, found by the metric's name.

A reader module states `UNIT`, `LAYER` (as PERF.md's list of layers has it),
`SOURCE` (device_trace, program_span, program_counter or host_clock) and
`MOVES` (the end-to-end metric it should move), and has one function
`read(art)` from the run's collected artefacts to a number. A reader that
finds nothing to read returns None and the harness leaves the metric out.

BENCHMARK.json allows a metric one `MOVES`, and a cell may report a per-layer
metric only beside the end-to-end metric it moves. So a reader shared by
cells that are judged on different metrics appears once per judged metric:
`open_<name>` (judged on request time) re-exports `<name>` (judged on
tokens per second), and the train cell's device metrics are `train_<name>`.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional


def load(name: str):
    return importlib.import_module(f"benchmark.layer_metrics.{name}")


def delta(art: Dict[str, Any], key: str) -> Optional[float]:
    """Change of an engine counter over the window."""
    a, b = art.get("stats_open"), art.get("stats_close")
    if not a or not b:
        return None
    return b[key] - a[key]

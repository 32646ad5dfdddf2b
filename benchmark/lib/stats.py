"""Order statistics for the benchmark's timings.

One rule for tails (choosing-metrics guide, section 1): a percentile is
reported only if at least ten samples lie beyond it, so a "p99" of forty
requests can never be a disguised maximum.
"""

from __future__ import annotations

import math
import os
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; `math.inf` samples (failed requests) sort last."""
    if not values:
        raise ValueError("percentile of no samples")
    s = sorted(values)
    pos = (len(s) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if lo == hi or math.isinf(s[hi]):
        return s[hi] if pos > lo else s[lo]
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def samples_beyond(n: int, q: float) -> int:
    """How many of n samples lie strictly above the q-th percentile's rank."""
    return n - 1 - math.floor((n - 1) * q / 100.0)


def cache_files(cache_dir: str) -> int:
    """Entries in JAX's persistent compile cache (its access-time markers
    left out): a count that grows means something was compiled."""
    return sum(1 for _r, _d, files in os.walk(cache_dir)
               for f in files if not f.endswith("-atime"))

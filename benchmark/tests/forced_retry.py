#!/usr/bin/env python3
"""benchmark/run.py with a host fault forced on a serve run's first window,
so that the second window can be seen end to end: here on the CPU
(test_rehearsal.py) and, by hand, on the chip.

    python3 benchmark/tests/forced_retry.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The first window is measured and judged as always; its verdict then gets one
"the generator ran late" fault more, the kind a stalled host leaves. Nothing
else is changed, so the run must ramp again, measure a second window on a
derived seed, report that one, and keep the first opening as `setup_s`."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.runners import serve_dp  # noqa: E402


def main(argv=None) -> int:
    real, windows = serve_dp.finish, []

    def finish(ctx, art):
        art = real(ctx, art)
        if not windows:
            art["faults"].add("late")
            art["problems"].append(
                "the generator ran late: forced by tests/forced_retry.py")
        windows.append(art["t_open"])
        return art

    serve_dp.finish = finish
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

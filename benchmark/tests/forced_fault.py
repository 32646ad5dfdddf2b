#!/usr/bin/env python3
"""benchmark/run.py with a fault of the system's planted in a serve run's
verdict (a request reported as failed), so that the end of a run that is not
`correct` can be seen whole: the reasons last on stderr and in the result.

    python3 benchmark/tests/forced_fault.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402
from benchmark.runners import serve_dp  # noqa: E402


def main(argv=None) -> int:
    real = serve_dp.finish

    def finish(ctx, art):
        art = real(ctx, art)
        art["faults"].add("failed")
        art["problems"].append(
            "1 requests failed: planted by tests/forced_fault.py")
        return art

    serve_dp.finish = finish
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""benchmark/run.py with PR 62's six readers of the engine loop's account
listed in the serve cells, and the counters they read passed through the
runner's `sum_stats`, until a `benchmark` PR makes both edits in the files
that hold them (a cell's list lives in `workloads/<cell>.json`, the keys a
runner passes in `runners/serve_dp.py` and the family runners' `COUNTERS`,
none of which another kind of PR may touch): here on the CPU
(test_step_account.py) and, by hand, on the chip.

    python3 benchmark/tests/listed_run_account.py --workload <cell> \
        --seed <n> --seconds <s> --trace <0|1>

A serve cell judged on tokens per second gets `CLOSED`, one judged on request
time `OPEN`; the run's `summary:` line then holds the counters themselves in
`stats_open` / `stats_close`. Nothing else is changed: a cell that serves
nothing, and a program without the counters, run as `run.py` runs them.
Delete this file with the edit (PERF.md section 7 has it word for word)."""

import argparse
import importlib
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

CLOSED = ["decode_turn_ms", "chunk_turn_ms", "engine_unwaited_turn_share",
          "engine_loop_stall_share"]
OPEN = ["open_decode_turn_ms", "open_engine_unwaited_turn_share"]
# what `PagedEngine.stats()` holds of the loop's account and its stalls, all
# sums over the engines (`loop_stall_last_at`, a time of day, is their latest)
SUMMED = re.compile(r"loop_(turn|wait|idle|stall|stall_admit)_s|loop_stalls"
                    r"|turns_unwaited|turn_unwaited_s|(steps|turn_s)_w\d+")


def listed(cell: dict) -> list:
    more = (CLOSED if "out_tokens_per_s" in cell["end_to_end"] else
            OPEN if "req_p50_s" in cell["end_to_end"] else [])
    return cell["per_layer"] + [m for m in more if m not in cell["per_layer"]]


def passing_the_account(sum_stats):
    def summed(per_rank):
        out = sum_stats(per_rank)
        for key in per_rank[0]:
            if SUMMED.fullmatch(key) and all(key in s for s in per_rank):
                out[key] = sum(s[key] for s in per_rank)
        if all("loop_stall_last_at" in s for s in per_rank):
            out["loop_stall_last_at"] = max(
                s["loop_stall_last_at"] for s in per_rank)
        return out

    return summed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload", required=True)
    cell_name = ap.parse_known_args(argv)[0].workload
    real = run.load_json

    def load_json(*parts):
        data = real(*parts)
        if parts[0] == "workloads":
            data["per_layer"] = listed(data)
        return data

    cell = real("workloads", f"{cell_name}.json")
    config = real("configs", f"{cell['config']}.json")
    runner = importlib.import_module(f"benchmark.runners.{config['runner']}")
    run.load_json = load_json
    if hasattr(runner, "sum_stats"):
        runner.sum_stats = passing_the_account(runner.sum_stats)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

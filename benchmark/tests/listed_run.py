#!/usr/bin/env python3
"""benchmark/run.py with PR 43's nine readers listed in the cells ISSUE 43
names for them, until a `benchmark` PR makes that edit in the cells' own
files (a cell's list lives in `workloads/<cell>.json`, which no other kind
of PR may touch): here on the CPU (test_xmeta.py) and, by hand, on the chip.

    python3 benchmark/tests/listed_run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Nothing else is changed: a cell not named below, and a name a cell already
lists, run as `run.py` runs them. Delete this file with the edit."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402

TRAIN = ["train_remat_share", "train_layers_fwd_share",
         "train_layers_bwd_share", "train_loss_share", "train_optimizer_share",
         "train_matmul_share", "train_matmul_mfu", "train_step_device_ms"]
OPEN = ["open_engine_idle_share"]
PENDING = {"internlm2-pretrain-4k-fsdp4": TRAIN, "tiny-pretrain-fsdp4": TRAIN,
           "mistral7b-chat-open": OPEN, "tiny-chat-open": OPEN}


def main(argv=None) -> int:
    real = run.load_json

    def load_json(*parts):
        data = real(*parts)
        if parts[0] == "workloads":
            listed = data["per_layer"]
            data["per_layer"] = listed + [
                m for m in PENDING.get(parts[1][:-len(".json")], [])
                if m not in listed]
        return data

    run.load_json = load_json
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())

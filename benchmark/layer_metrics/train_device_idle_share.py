"""`device_idle_share` in a cell that is judged on trained tokens per second."""
from benchmark.layer_metrics.device_idle_share import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "train_tokens_per_s"

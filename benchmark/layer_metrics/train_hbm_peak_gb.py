"""`hbm_peak_gb` in a cell that is judged on trained tokens per second."""
from benchmark.layer_metrics.hbm_peak_gb import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "train_tokens_per_s"

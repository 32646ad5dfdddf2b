"""`hbm_peak_gb` in a cell that is judged on request time."""
from benchmark.layer_metrics.hbm_peak_gb import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

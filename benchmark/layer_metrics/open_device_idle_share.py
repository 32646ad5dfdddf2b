"""`device_idle_share` in a cell that is judged on request time."""
from benchmark.layer_metrics.device_idle_share import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

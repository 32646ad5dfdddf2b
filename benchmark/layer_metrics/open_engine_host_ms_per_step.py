"""`engine_host_ms_per_step` in a cell that is judged on request time."""
from benchmark.layer_metrics.engine_host_ms_per_step import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

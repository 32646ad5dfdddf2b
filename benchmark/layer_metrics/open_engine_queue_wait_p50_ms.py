"""`engine_queue_wait_p50_ms` in a cell that is judged on request time."""
from benchmark.layer_metrics.engine_queue_wait_p50_ms import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

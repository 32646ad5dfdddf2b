"""`decode_turn_ms` in a cell that is judged on request time."""
from benchmark.layer_metrics.decode_turn_ms import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

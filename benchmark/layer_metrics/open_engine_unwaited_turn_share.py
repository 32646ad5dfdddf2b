"""`engine_unwaited_turn_share` in a cell that is judged on request time."""
from benchmark.layer_metrics.engine_unwaited_turn_share import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

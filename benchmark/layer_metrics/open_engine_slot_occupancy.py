"""`engine_slot_occupancy` in a cell that is judged on request time."""
from benchmark.layer_metrics.engine_slot_occupancy import LAYER, SOURCE, UNIT, read  # noqa: F401

MOVES = "req_p50_s"

"""Share of the traced window in which no operation ran on the chip: 1 - the
union of the device operations' intervals over the window, averaged over the
chips. The trace is taken in the process that holds the chip."""
UNIT, LAYER, SOURCE, MOVES = "%", "device", "device_trace", "out_tokens_per_s"


def read(art):
    t = art.get("trace")
    return None if not t else 100.0 * t["idle_share"]

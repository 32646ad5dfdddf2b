"""Share of the device's busy time spent in Pallas kernels (the flash
attention forward, dq and dkv calls of ops/flash_attention.py)."""
UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "train_tokens_per_s"


def read(art):
    t = art.get("trace")
    if not t or not t["pallas_s"]:
        return None
    return 100.0 * t["pallas_s"] / t["busy_s"]

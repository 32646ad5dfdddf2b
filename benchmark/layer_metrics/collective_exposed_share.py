"""Share of the traced window in which a chip's core sat in a collective
(all-gather, reduce-scatter, all-reduce, their -start and -done) and so
computed nothing: the communication that overlap did not hide."""
UNIT, LAYER, SOURCE, MOVES = "%", "collectives", "device_trace", "train_tokens_per_s"


def read(art):
    t = art.get("trace")
    if not t or t["chips"] < 2:
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]

"""Share of the device's busy time spent in the `retention` scope
(`jax.named_scope("retention")` in ray_tpu/models/brumby.py): the
projections, the q/k norms, the rotary embedding, the gate, the decode
kernel `retention_step`, the chunk's `retention_chunked` and W_o. Read from
the trace's own `tf_op` (lib/scopes_solar.py, which takes any scope name)."""
from benchmark.lib import scopes_solar

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"


def read(art):
    return scopes_solar.share(art, "retention")

"""Share of the device's busy time spent in the `attn_window` scope
(`jax.named_scope("attn_window")` in ray_tpu/llm/_mellum_steps.py and
models/mellum.py): the sliding-window layers' projections, q/k norm, plain
rotary, the write into the slot's ring, the paged kernel over the window's
pages, the chunk's windowed attention and W_o. Read from the trace's own
`tf_op` (lib/scopes_solar.py)."""
from benchmark.lib import scopes_solar

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"


def read(art):
    return scopes_solar.share(art, "attn_window")

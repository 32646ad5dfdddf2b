"""Share of the device's busy time spent in the `attn_full` scope
(`jax.named_scope("attn_full")` in ray_tpu/llm/_mellum_steps.py and
models/mellum.py): the full layers' projections, q/k norm, YaRN rotary, the
scatter into the block pool, the paged kernel over every live page, the
chunk's attention over its whole sequence and W_o. Two of eight layers, and
the only ones whose time grows with the context. Read from the trace's own
`tf_op` (lib/scopes_solar.py)."""
from benchmark.lib import scopes_solar

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"


def read(art):
    return scopes_solar.share(art, "attn_full")

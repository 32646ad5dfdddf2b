"""Share of the device's busy time spent in the `gqa` scope
(`jax.named_scope("gqa")` in ray_tpu/models/solar.py and
llm/_solar_steps.py): the softmax layers' projections, the scatter into the
pool, the paged kernel of the decode rows, the chunk's attention, the gate
and W_o. Read from the trace's own `tf_op` (lib/scopes_solar.py), since
lib/scopes.py knows its scopes by a fixed tuple and counts this one as
`rest`: kda + moe + gqa + (rest - gqa) is the busy time."""
from benchmark.lib import scopes_solar

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"


def read(art):
    return scopes_solar.share(art, "gqa")

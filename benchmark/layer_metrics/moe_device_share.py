"""Share of the device's busy time spent in the `moe` scope
(`jax.named_scope("moe")` in ray_tpu/models/ling.py and
llm/_ling_steps.py), decode steps and prefills alike: lib/scopes.py joins the
trace's operations with the compiled steps' `op_name` metadata. With the two
other scopes and the rest it sums to the busy time."""
from benchmark.lib import scopes

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"


def read(art):
    return scopes.share(art, "moe")

"""Operations and bytes the algorithm needs, computed from shapes.

These are the yardstick's own: a later PR cannot move a utilization by
recounting. `hp` is a configuration file's dict with the published keys
(hidden_size, num_hidden_layers, num_attention_heads, num_key_value_heads,
intermediate_size, vocab_size).

Conventions: one multiply-add is 2 operations. Recomputed work
(rematerialisation, the flash backward's second pass over the scores) is
work the chip does and the model does not need, so it is never counted.
bench.py counted 6 x num_params() including `tok_emb`: the embedding lookup
is a gather, not a matmul, and is left out here.
"""

from __future__ import annotations


def head_dim(hp: dict) -> int:
    return hp.get("head_dim") or hp["hidden_size"] // hp["num_attention_heads"]


def matmul_params(hp: dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' seven projections and the output head. Norm vectors and the
    embedding table (a lookup) are not."""
    d, hd = hp["hidden_size"], head_dim(hp)
    q = d * hp["num_attention_heads"] * hd
    kv = 2 * d * hp["num_key_value_heads"] * hd
    o = hp["num_attention_heads"] * hd * d
    ffn = 3 * d * hp["intermediate_size"]
    return hp["num_hidden_layers"] * (q + kv + o + ffn) + d * hp["vocab_size"]


def total_params(hp: dict) -> int:
    d = hp["hidden_size"]
    norms = hp["num_hidden_layers"] * 2 * d + d
    return matmul_params(hp) + hp["vocab_size"] * d + norms


def attention_flops_fwd(hp: dict, seq: int, causal: bool = True) -> float:
    """QK^T and PV for one sequence through every layer, forward only.
    Causal attention needs half the square."""
    per_layer = 4.0 * hp["num_attention_heads"] * head_dim(hp) * seq * seq
    return hp["num_hidden_layers"] * per_layer * (0.5 if causal else 1.0)


def train_flops_per_token(hp: dict, seq: int) -> float:
    """Forward + backward = 3 x forward: 6 per matmul parameter, plus the
    attention scores' share per token at this sequence length."""
    return 6.0 * matmul_params(hp) + 3.0 * attention_flops_fwd(hp, seq) / seq


def flash_kernel_cost(kind: str, *, batch: int, heads: int, kv_heads: int,
                      sq: int, sk: int, hd: int, itemsize: int = 2,
                      causal: bool = True) -> dict:
    """Least work of one flash-attention kernel call: `flops` and HBM
    `bytes` (each operand read once, each result written once).

    kind: "fwd" (QK^T, PV), "dq" (recompute QK^T, dP = dO V^T, dQ = dS K),
    "dkv" (recompute QK^T, dP, dV = P^T dO, dK = dS^T Q). The recomputed
    QK^T and dP in the two backward kernels are the algorithm's own (flash
    backward stores no scores), so they count here, per kernel.
    """
    square = batch * heads * sq * sk * hd * (0.5 if causal else 1.0)
    matmuls = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    q_bytes = batch * heads * sq * hd * itemsize
    kv_bytes = 2 * batch * kv_heads * sk * hd * itemsize
    rows = batch * heads * sq * 4          # lse / delta rows, float32
    moved = {
        "fwd": q_bytes + kv_bytes + q_bytes + rows,            # q,k,v -> o,lse
        "dq": 2 * q_bytes + kv_bytes + 2 * rows + q_bytes,     # q,do,k,v -> dq
        "dkv": 2 * q_bytes + kv_bytes + 2 * rows + kv_bytes,   # -> dk,dv
    }[kind]
    return {"flops": 2.0 * matmuls * square, "bytes": float(moved)}


def roofline_seconds(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take for `cost`, and which roof sets
    it."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}

"""A configuration file's published sizes, as the program wants them."""

from __future__ import annotations

from typing import Any, Dict


class CellFailure(AssertionError):
    """The cell ran and something it must guarantee did not hold."""


def published(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes of a configuration file: its top-level numbers."""
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def model_overrides(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The published keys of a configuration file as LlamaConfig fields."""
    import jax.numpy as jnp  # dtype names only: no backend is touched

    m, prog = cfg, cfg["program"]
    heads = m["num_attention_heads"]
    if m.get("head_dim", m["hidden_size"] // heads) != m["hidden_size"] // heads:
        raise CellFailure("models/llama.py fixes head_dim = hidden_size / heads")
    return {
        "vocab_size": m["vocab_size"], "dim": m["hidden_size"],
        "n_layers": m["num_hidden_layers"], "n_heads": heads,
        "n_kv_heads": m["num_key_value_heads"],
        "ffn_dim": m["intermediate_size"], "rope_theta": m["rope_theta"],
        "norm_eps": m["rms_norm_eps"],
        "max_seq_len": prog["max_seq_len"],
        "dtype": getattr(jnp, prog["dtype"]),
        "param_dtype": getattr(jnp, prog["param_dtype"]),
        **({"attention_impl": prog["attention_impl"]}
           if "attention_impl" in prog else {}),
    }

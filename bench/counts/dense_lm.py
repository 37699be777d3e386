"""Needed FLOPs and cache bytes of a dense decoder-only language model
(llama layout: GQA attention with rotary positions, gated MLP, tied or
untied head).

Counts are of the work the model needs, independent of how the program
schedules it: decode attention over the live context only (not the
cache's capacity), the output head only where a token is sampled, one
multiply-add as two FLOPs.  Elementwise work (norms, rotary, softmax,
activations) is left out, as is the embedding lookup.

The configuration is the benchmark's configuration file (Hugging Face
key names).
"""

from __future__ import annotations

from typing import Dict


def _dims(cfg: Dict) -> Dict[str, int]:
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
            "hd": int(cfg.get("head_dim") or d // h),
            "ff": int(cfg["intermediate_size"]),
            "L": int(cfg["num_hidden_layers"]), "V": int(cfg["vocab_size"])}


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one token multiplies through in one layer."""
    k = _dims(cfg)
    attn = k["d"] * k["hd"] * (2 * k["h"] + 2 * k["kv"])   # q, o, k, v
    mlp = 3 * k["d"] * k["ff"]                              # gate, up, down
    return attn + mlp


def head_params(cfg: Dict) -> int:
    k = _dims(cfg)
    return k["d"] * k["V"]


def attention_flops(cfg: Dict, n_keys: int) -> float:
    """Scores and weighted values of one query over ``n_keys`` keys, all
    layers and heads."""
    k = _dims(cfg)
    return 4.0 * k["L"] * k["h"] * k["hd"] * n_keys


def decode_token_flops(cfg: Dict, position: int) -> float:
    """One decoded token at 0-based ``position`` (it attends over
    ``position + 1`` keys, itself included), head included."""
    k = _dims(cfg)
    return 2.0 * (k["L"] * layer_matmul_params(cfg) + head_params(cfg)) \
        + attention_flops(cfg, position + 1)


_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
                "float8_e4m3fn": 1}


def kv_bytes(cfg: Dict, n_keys: int) -> float:
    """Keys and values of ``n_keys`` positions, all layers, at the cache's
    dtype (``dtypes.kv_cache``)."""
    k = _dims(cfg)
    per = _DTYPE_BYTES[cfg["dtypes"]["kv_cache"]]
    return float(per * 2 * k["L"] * k["kv"] * k["hd"] * n_keys)


__all__ = ["attention_flops", "decode_token_flops", "head_params", "kv_bytes",
           "layer_matmul_params"]

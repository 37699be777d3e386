"""Random weights of a dense decoder from ``--seed``, made on the device
in one jitted call, in the dtype the program takes them (float32).

The tree has the layout the serving program reads (layers stacked on a
leading axis: ``embed``, ``ln_f``, ``layers/{ln1, attn, ln2, ffn}``); the
reference (:mod:`bench.reference.dense_lm`) reads the same tree.  Scales:
0.02 for the embedding, 1/sqrt(fan_in) for every projection, norm gains
1 + 0.1 N(0, 1).  The projections back into the residual stream are not
divided by sqrt(2 L) as in GPT-2's initialisation: with that, the
program's embedding multiplier (sqrt(hidden_size)) leaves the residual
stream all but the input embedding, the model echoes its input with a
margin of ~6 logits, and no precision, however low, changes a token, so
the check could not tell bfloat16 from float8.
"""

from __future__ import annotations

import math
from typing import Dict


def dense_lm(cfg: Dict, seed: int, device=None):
    import jax
    import jax.numpy as jnp

    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kv, ff = int(cfg["num_key_value_heads"]), int(cfg["intermediate_size"])
    hd = int(cfg.get("head_dim") or d // h)
    L, V = int(cfg["num_hidden_layers"]), int(cfg["vocab_size"])
    shapes = {
        "embed": ((V, d), 0.02),
        "ln_f/w": ((d,), None),
        "layers/ln1/w": ((L, d), None),
        "layers/ln2/w": ((L, d), None),
        "layers/attn/wq": ((L, d, h, hd), 1 / math.sqrt(d)),
        "layers/attn/wk": ((L, d, kv, hd), 1 / math.sqrt(d)),
        "layers/attn/wv": ((L, d, kv, hd), 1 / math.sqrt(d)),
        "layers/attn/wo": ((L, h, hd, d), 1 / math.sqrt(h * hd)),
        "layers/ffn/w_gate": ((L, d, ff), 1 / math.sqrt(d)),
        "layers/ffn/w_up": ((L, d, ff), 1 / math.sqrt(d)),
        "layers/ffn/w_down": ((L, ff, d), 1 / math.sqrt(ff)),
    }

    def make(key):
        tree: Dict = {}
        for i, (path, (shape, scale)) in enumerate(sorted(shapes.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = 1.0 + 0.1 * x if scale is None else x * scale
            node = tree
            *parents, leaf = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[leaf] = x
        return tree

    key = jax.random.key(int(seed))
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(make)(key)


__all__ = ["dense_lm"]

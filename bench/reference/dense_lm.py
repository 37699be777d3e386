"""Plain float32 forward pass of a dense decoder (SmolLM / llama layout),
written from the published architecture and the configuration file alone.

    x = embed[tokens] * embedding_multiplier
    per layer:  x += Attn(RMSNorm(x)) ;  x += MLP(RMSNorm(x))
    logits = RMSNorm(x) @ embed.T            (tied embeddings)

RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g.  Attention: grouped-query
(query head ``i`` reads key/value head ``i // (heads / kv_heads)``),
rotary positions on the two halves of each head (theta = ``rope_theta``),
scores scaled by 1/sqrt(head_dim), causal softmax.  MLP:
``down(silu(gate(x)) * up(x))``.

Every matrix product runs at ``Precision.HIGHEST`` in float32, one
sequence at a time, layer by layer (a scan over the stacked layers).  The
``fp8`` variant is the control: the same pass with both operands of every
matrix product rounded to float8 e4m3 (one scale per tensor) and float32
accumulation.  Nothing here imports the program.
"""

from __future__ import annotations

import functools
import math
from typing import Dict

import numpy as np


def _quant8(x):
    import jax.numpy as jnp
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8: bool):
    import jax
    import jax.numpy as jnp
    if fp8:
        a, b = _quant8(a), _quant8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (S, H, D) at positions 0..S-1."""
    import jax.numpy as jnp
    S, _, D = x.shape
    half = D // 2
    inv = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                  / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.lru_cache(maxsize=8)
def _forward_fn(dims: tuple, fp8: bool):
    import jax
    import jax.numpy as jnp
    (h, kv, hd, eps, theta, mult) = dims
    rep = h // kv

    def layer(x, p):
        S = x.shape[0]
        a = _rms(x, p["ln1"]["w"], eps)
        q = _rope(_mm("sd,dhk->shk", a, p["attn"]["wq"], fp8), theta)
        k = _rope(_mm("sd,dhk->shk", a, p["attn"]["wk"], fp8), theta)
        v = _mm("sd,dhk->shk", a, p["attn"]["wv"], fp8)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
        s = _mm("qhk,shk->hqs", q, k, fp8) / math.sqrt(hd)
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        s = jnp.where(causal[None], s, -jnp.inf)
        pr = jax.nn.softmax(s, axis=-1)
        o = _mm("hqs,shk->qhk", pr, v, fp8)
        x = x + _mm("shk,hkd->sd", o, p["attn"]["wo"], fp8)
        m = _rms(x, p["ln2"]["w"], eps)
        g = _mm("sd,df->sf", m, p["ffn"]["w_gate"], fp8)
        u = _mm("sd,df->sf", m, p["ffn"]["w_up"], fp8)
        act = g * jax.nn.sigmoid(g) * u
        return x + _mm("sf,fd->sd", act, p["ffn"]["w_down"], fp8), None

    def forward(params, tokens):
        x = params["embed"][tokens] * mult
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _rms(x, params["ln_f"]["w"], eps)
        return _mm("sd,vd->sv", x, params["embed"], fp8)

    def gaps(params, tokens, positions, served):
        """Reference logits at ``positions``: the gap by which each
        served token's logit lies below the best, and the argmax."""
        logits = forward(params, tokens)[positions]
        best = jnp.max(logits, axis=-1)
        got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
        return best - got, jnp.argmax(logits, axis=-1)

    return jax.jit(gaps), jax.jit(forward)


def _dims(cfg: Dict) -> tuple:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return (h, int(cfg["num_key_value_heads"]),
            int(cfg.get("head_dim") or d // h), float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), float(cfg["embedding_multiplier"]))


def served_gaps(params, cfg: Dict, prompt, served, pad_to: int,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Run the reference once over ``prompt`` + ``served`` tokens (padded
    to ``pad_to``, which causal attention makes exact) and return, for
    each served token, the gap below the float32 reference's best logit.
    The positions read are padded to ``pad_to`` as well, so one compiled
    program serves every request.

    With ``control`` the float8 pass runs as well, and ``control_gaps``
    holds the float32 gap of the token the float8 pass puts first at
    each of the same positions."""
    import jax.numpy as jnp
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    if len(seq) > pad_to:
        raise ValueError(f"sequence of {len(seq)} > pad_to {pad_to}")
    n = len(served)
    tokens = np.zeros(pad_to, np.int32)
    tokens[:len(seq)] = seq
    positions = np.zeros(pad_to, np.int32)
    positions[:n] = np.arange(len(prompt) - 1, len(seq))
    picked = np.zeros(pad_to, np.int32)
    picked[:n] = served
    args = (jnp.asarray(tokens), jnp.asarray(positions))
    gaps_fn = _forward_fn(_dims(cfg), False)[0]
    gap, _ = gaps_fn(params, *args, jnp.asarray(picked))
    out = {"gaps": np.asarray(gap)[:n]}
    if control:
        _, top8 = _forward_fn(_dims(cfg), True)[0](params, *args,
                                                   jnp.asarray(picked))
        cgap, _ = gaps_fn(params, *args, top8)
        out["control_gaps"] = np.asarray(cgap)[:n]
    return out


def reference_logits(params, cfg: Dict, tokens) -> np.ndarray:
    """Float32 reference logits ``(len(tokens), vocab)`` of one sequence."""
    import jax.numpy as jnp
    fwd = _forward_fn(_dims(cfg), False)[1]
    return np.asarray(fwd(params, jnp.asarray(np.asarray(tokens, np.int32))))


__all__ = ["reference_logits", "served_gaps"]

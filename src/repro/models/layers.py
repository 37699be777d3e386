"""Model layers, pure-JAX, sharding-annotated via logical axis names.

Every mixer here has the same split pocl imposes on its kernel compiler:
the *math* is target-independent, and the *mapping* (which mesh axis each
tensor dim lands on) comes from the ShardingRules table, threaded through
``constrain``.  Kernels (Pallas) are swapped in at the ops.py dispatch
layer, mirroring pocl's device-specific builtin libraries.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingRules, constrain
from repro.kernels import ops
from repro import vml
from .config import ModelConfig

Params = Dict[str, jnp.ndarray]


def scoped(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``, so
    its ops carry the name in their metadata and a profile's ``tf_op``
    (``.../while/body/attention/...``).  Trace-time only: the compiled
    program runs the same."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

@scoped("norm")
def norm(x, p: Params, cfg: ModelConfig, eps: float = 1e-6):
    if "b" in p:                                   # layernorm
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["w"].astype(jnp.float32)
                + p["b"].astype(jnp.float32)).astype(x.dtype)
    return ops.rmsnorm(x, p["w"], eps=eps, use_pallas=cfg.use_pallas)


def activation(x, cfg: ModelConfig):
    if cfg.use_vml_act:
        return vml.silu(x) if cfg.act == "silu" else vml.gelu_tanh(x)
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def rope(x, positions, theta: float):
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


from .flash import blocked_attention  # noqa: E402  (memory-efficient custom-VJP attention)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

@scoped("attention")
def attention(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
              positions, causal: bool = True, kv_x=None,
              use_rope: bool = True,
              cache: Optional[Tuple] = None):
    """Self- or cross-attention.  cache=(k_cache, v_cache, lengths) with
    layout (B, S_cache, KV, D); returns (out, new_cache)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = constrain(q, rules, "batch", "seq", "heads", "head_dim")
    src = kv_x if kv_x is not None else x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    k = constrain(k, rules, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, rules, "batch", "seq", "kv_heads", "head_dim")

    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        if kv_x is None:
            k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and kv_x is None:
        k_cache, v_cache, lengths = cache
        if S == 1:
            # decode: append one token then attend over the cache.
            # Cache layout is natively (B, KV, S, D) — the attention
            # kernel's layout — so NO per-step full-cache transpose
            # happens (§Perf H1 iteration 2).  With S sharded
            # ("cache_seq"), XLA turns the softmax over the sharded S
            # into partial max/sum + tiny all-reduces = flash-decoding.
            # Each batch row writes at its OWN length: continuous-batching
            # slots sit at different sequence positions (docs/serving.md),
            # so the write index is per-row, not lengths[0] for the group.
            row_idx = jnp.broadcast_to(jnp.asarray(lengths), (B,))
            row_update = jax.vmap(
                lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
                    c, n, i, axis=1))
            with jax.named_scope("cache_update"):
                k_cache = row_update(
                    k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype),
                    row_idx)
                v_cache = row_update(
                    v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype),
                    row_idx)
            kq = jnp.squeeze(q, axis=1)              # (B,H,D)
            o = ops.decode_attention(kq, k_cache, v_cache,
                                     lengths + 1, use_pallas=cfg.use_pallas)
            out = o[:, None]                          # (B,1,H,D)
            new_cache = (k_cache, v_cache, lengths + 1)
        else:
            # prefill: attend causally over fresh K/V, then write the cache
            # (one transpose for the whole prompt, not one per step)
            out = blocked_attention(q, k, v, causal=True,
                                    block_q=cfg.attn_block_q,
                                    block_k=cfg.attn_block_k)
            with jax.named_scope("cache_update"):
                k_cache = jax.lax.dynamic_update_slice_in_dim(
                    k_cache, k.transpose(0, 2, 1, 3).astype(k_cache.dtype),
                    0, axis=2)
                v_cache = jax.lax.dynamic_update_slice_in_dim(
                    v_cache, v.transpose(0, 2, 1, 3).astype(v_cache.dtype),
                    0, axis=2)
            new_cache = (k_cache, v_cache, lengths + S)
    else:
        if cfg.use_pallas and S <= 4096 and kv_x is None:
            out = ops.attention(q, k, v, causal=causal, use_pallas=True)
        else:
            out = blocked_attention(q, k, v, causal=causal and kv_x is None,
                                    block_q=cfg.attn_block_q,
                                    block_k=cfg.attn_block_k)

    out = constrain(out, rules, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = constrain(y, rules, "batch", "act_seq", "d_model")
    return y, new_cache


# ---------------------------------------------------------------------------
# FFN: dense MLP and MoE
# ---------------------------------------------------------------------------

@scoped("mlp")
def mlp(x, p: Params, cfg: ModelConfig, rules: ShardingRules):
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = activation(g, cfg) * h
    else:
        h = activation(h, cfg)
    h = constrain(h, rules, "batch", "seq", "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return constrain(y, rules, "batch", "act_seq", "d_model")


def moe(x, p: Params, cfg: ModelConfig, rules: ShardingRules):
    """Token-choice top-k MoE with capacity dropping (GShard-style dispatch
    einsums).  Tokens are chunked into groups of ``cfg.moe_group`` so the
    dispatch tensor is O(group² · k · cf) per group instead of O(S·E·C).
    Experts shard over the 'experts' axis (EP) when divisible, otherwise
    per-expert FFN dims shard over 'expert_mlp' (TP fallback)."""
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    g = min(cfg.moe_group, S)
    pad = (-S) % g
    if pad:   # pad to a group multiple; padded tokens never claim capacity
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    G = (B * Sp) // g
    C = max(1, int(g * K * cfg.capacity_factor / E))

    xt = x.reshape(G, g, d)
    valid = (jnp.arange(Sp) < S)
    valid = jnp.broadcast_to(valid[None], (B, Sp)).reshape(G, g)
    logits = jnp.einsum("gsd,de->gse", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)          # (G,g,K)
    gate_vals = gate_vals / jnp.clip(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    gate_vals = gate_vals * valid[..., None]

    # position of each (token, k) inside its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32) \
        * valid[..., None, None]                           # (G,g,K,E)
    pos = jnp.cumsum(onehot.reshape(G, g * K, E), axis=1).reshape(
        G, g, K, E) - 1
    pos = jnp.sum(pos * onehot, axis=-1)                   # (G,g,K)
    keep = pos < C

    # dispatch: (G,g,E,C) one-hot over (expert, slot)
    disp = jnp.zeros((G, g, E, C), x.dtype)
    comb = jnp.zeros((G, g, E, C), jnp.float32)
    for kk in range(K):
        sel = jax.nn.one_hot(gate_idx[..., kk], E, dtype=x.dtype) \
            * keep[..., kk, None] * valid[..., None]
        slot = jax.nn.one_hot(pos[..., kk], C, dtype=x.dtype)
        contrib = sel[..., None] * slot[..., None, :]
        disp = disp + contrib
        comb = comb + contrib.astype(jnp.float32) \
            * gate_vals[..., kk, None, None]

    xin = jnp.einsum("gsec,gsd->egcd", disp, xt)
    # the token-group dim stays sharded on the data axis: the dispatch is
    # an all-to-all over (data -> experts), NOT a gather of all tokens.
    # "moe_capacity" optionally shards the capacity dim over the model
    # axis (token-parallel MoE; see launch/variants.py).
    xin = constrain(xin, rules, "experts", "batch", "moe_capacity",
                    "d_model")
    up = jnp.einsum("egcd,edf->egcf", xin, p["w_up"])
    gt = jnp.einsum("egcd,edf->egcf", xin, p["w_gate"])
    h = activation(gt, cfg) * up
    h = constrain(h, rules, "experts", "batch", "moe_capacity",
                  "expert_mlp")
    eo = jnp.einsum("egcf,efd->egcd", h, p["w_down"])
    eo = constrain(eo, rules, "experts", "batch", "moe_capacity",
                   "d_model")
    y = jnp.einsum("gsec,egcd->gsd", comb.astype(x.dtype), eo)

    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=1)                           # (G,E)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(gate_idx, E, dtype=jnp.float32), axis=2),
        axis=1) / K
    aux = E * jnp.mean(jnp.sum(me * ce, axis=-1))
    y = y.reshape(B, Sp, d)[:, :S]
    y = constrain(y, rules, "batch", "act_seq", "d_model")
    return y, aux


# ---------------------------------------------------------------------------
# Mamba-2 (SSD) mixer
# ---------------------------------------------------------------------------

def _causal_conv(u, w, b, state=None):
    """Depthwise causal conv.  u: (B,S,C), w: (W,C).  With ``state``
    ((B,W-1,C)) performs a streaming step update (decode)."""
    W = w.shape[0]
    if state is not None:
        window = jnp.concatenate([state, u], axis=1)       # (B,W,C) for S=1
        y = jnp.einsum("bwc,wc->bc", window[:, -W:], w) + b
        return y[:, None], window[:, 1:]
    pad = jnp.pad(u, ((0, 0), (W - 1, 0), (0, 0)))
    y = sum(pad[:, i:i + u.shape[1]] * w[i] for i in range(W)) + b
    return y, None


def mamba2(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
           cache: Optional[Tuple] = None):
    """Mamba-2 SSD mixer.  cache=(conv_x, conv_B, conv_C, ssd_state) for
    decode; returns (out, new_cache)."""
    B, S, _ = x.shape
    Hh, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    Gq = cfg.ssm_groups

    z = jnp.einsum("bsd,di->bsi", x, p["w_z"])
    u = jnp.einsum("bsd,di->bsi", x, p["w_x"])
    Bp = jnp.einsum("bsd,dn->bsn", x, p["w_B"])
    Cp = jnp.einsum("bsd,dn->bsn", x, p["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    u = constrain(u, rules, "batch", "seq", "conv_dim")

    decode = cache is not None and S == 1
    cx = cB = cC = st = None
    if decode:
        cx, cB, cC, st = cache
    # conv state = the last (W-1) PRE-conv inputs (streaming window)
    W = cfg.ssm_conv
    u_raw, B_raw, C_raw = u, Bp, Cp
    u, ncx = _causal_conv(u, p["conv_x_w"], p["conv_x_b"], cx)
    Bp, ncB = _causal_conv(Bp, p["conv_B_w"], p["conv_B_b"], cB)
    Cp, ncC = _causal_conv(Cp, p["conv_C_w"], p["conv_C_b"], cC)
    u = vml.silu(u) if cfg.use_vml_act else jax.nn.silu(u)
    Bp = vml.silu(Bp) if cfg.use_vml_act else jax.nn.silu(Bp)
    Cp = vml.silu(Cp) if cfg.use_vml_act else jax.nn.silu(Cp)

    xs = u.reshape(B, S, Hh, P)
    xs = constrain(xs, rules, "batch", "seq", "ssm_heads", None)
    Bm = Bp.reshape(B, S, Gq, N)
    Cm = Cp.reshape(B, S, Gq, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    new_cache = None
    if decode:
        y, new_state = ops.ref.ssd_decode_step(
            st, xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
        y = y[:, None]
        new_cache = (ncx, ncB, ncC, new_state)
    else:
        pad = (-S) % cfg.ssm_chunk
        if pad:
            # pad the scan to a chunk multiple (padded steps only decay the
            # state, and y/state for them are discarded) — prefill requires
            # an exact multiple so the cached state is exact
            assert cache is None, "prefill seq must be a ssm_chunk multiple"
            xs_p = jnp.pad(xs, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt_p = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            Bm_p = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            Cm_p = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
            y, final_state = ops.ssd_scan(xs_p, dt_p, A, Bm_p, Cm_p,
                                          chunk=cfg.ssm_chunk,
                                          use_pallas=cfg.use_pallas)
            y = y[:, :S]
        else:
            y, final_state = ops.ssd_scan(xs, dt, A, Bm, Cm,
                                          chunk=cfg.ssm_chunk,
                                          use_pallas=cfg.use_pallas)
        if cache is not None:   # prefill: stash streaming window + state
            new_cache = (u_raw[:, S - W + 1:], B_raw[:, S - W + 1:],
                         C_raw[:, S - W + 1:], final_state)

    y = y.astype(x.dtype) + xs * p["D"][None, None, :, None].astype(xs.dtype)
    y = y.reshape(B, S, Hh * P)
    # gated RMSNorm (Mamba-2 norm before out-proj)
    y = ops.rmsnorm(y * (vml.silu(z) if cfg.use_vml_act else jax.nn.silu(z)),
                    p["norm_w"], use_pallas=cfg.use_pallas)
    out = jnp.einsum("bsi,id->bsd", y, p["w_out"])
    return constrain(out, rules, "batch", "act_seq", "d_model"), new_cache


# ---------------------------------------------------------------------------
# residual blocks
# ---------------------------------------------------------------------------

def attn_block(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
               positions, causal=True, use_rope=True, cache=None):
    """pre-norm attention + FFN block; returns (x, aux_loss, new_cache)."""
    h, new_cache = attention(norm(x, p["ln1"], cfg), p["attn"], cfg, rules,
                             positions=positions, causal=causal,
                             use_rope=use_rope, cache=cache)
    x = x + h
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe" and "router" in p["ffn"]:
        h, aux = moe(norm(x, p["ln2"], cfg), p["ffn"], cfg, rules)
    else:
        h = mlp(norm(x, p["ln2"], cfg), p["ffn"], cfg, rules)
    return x + h, aux, new_cache


def mamba_block(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
                cache=None):
    h, new_cache = mamba2(norm(x, p["ln1"], cfg), p["mixer"], cfg, rules,
                          cache=cache)
    return x + h, new_cache


def cross_block(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
                kv_x, positions):
    """Gated cross-attention block (llama-3.2-vision style)."""
    h, _ = attention(norm(x, p["ln"], cfg), p["xattn"], cfg, rules,
                     positions=positions, causal=False, kv_x=kv_x,
                     use_rope=False)
    return x + (jnp.tanh(p["gate"].astype(jnp.float32)) * h).astype(x.dtype)


def encdec_block(x, p: Params, cfg: ModelConfig, rules: ShardingRules, *,
                 enc_out, positions, cache=None):
    """Whisper decoder block: self-attn + cross-attn + FFN."""
    h, new_cache = attention(norm(x, p["ln1"], cfg), p["attn"], cfg, rules,
                             positions=positions, causal=True,
                             use_rope=False, cache=cache)
    x = x + h
    h, _ = attention(norm(x, p["lnx"], cfg), p["xattn"], cfg, rules,
                     positions=positions, causal=False, kv_x=enc_out,
                     use_rope=False)
    x = x + h
    h = mlp(norm(x, p["ln2"], cfg), p["ffn"], cfg, rules)
    return x + h, new_cache

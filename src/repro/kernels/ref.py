"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each kernel's tests sweep shapes/dtypes
and assert allclose against these.  They are also the default model path on
CPU and inside the multi-pod dry-run (XLA shards/fuses them well, and their
HLO FLOPs feed the roofline analysis).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def repeat_kv(k: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """(B, Hkv, S, D) -> (B, Hkv*n_rep, S, D) for GQA."""
    if n_rep == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, n_rep, s, d)) \
        .reshape(b, h * n_rep, s, d)


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              causal: bool = True, sm_scale: Optional[float] = None,
              bias: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full attention.  q: (B, H, Sq, D); k, v: (B, Hkv, Sk, D)."""
    B, H, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    k = repeat_kv(k, H // Hkv)
    v = repeat_kv(v, H // Hkv)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        qi = jnp.arange(Sq)[:, None] + (Sk - Sq)
        ki = jnp.arange(Sk)[None, :]
        s = jnp.where(ki <= qi, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, lengths: jnp.ndarray,
                     sm_scale: Optional[float] = None) -> jnp.ndarray:
    """One-token attention against a KV cache.

    q: (B, H, D); caches: (B, Hkv, S, D); lengths: (B,) valid prefix sizes.
    """
    B, H, D = q.shape
    Hkv, S = k_cache.shape[1], k_cache.shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(D)
    # GQA without materializing repeated K/V: group the query heads.
    # Keeping the cache un-broadcast lets the SPMD partitioner keep its
    # sequence sharding (flash-decoding: partial softmax + tiny
    # all-reduces) instead of replicating the cache.
    rep = H // Hkv
    qg = q.reshape(B, Hkv, rep, D)
    # dot in the cache dtype (MXU accumulates f32 internally); upcasting
    # the operands instead would materialize an f32 copy of the WHOLE
    # cache — scores are tiny, casting them is free
    s = jnp.einsum("bgrd,bgsd->bgrs", qg,
                   k_cache).astype(jnp.float32) * scale
    mask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    s = jnp.where(mask, s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrs,bgsd->bgrd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, H, D)


def layer_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, lengths: jnp.ndarray,
                           layer) -> jnp.ndarray:
    """:func:`decode_attention` over layer ``layer`` of stacked caches
    in the decode step's layout, (L, B, Hkv, D, S)."""
    def view(c):
        return jnp.swapaxes(
            jax.lax.dynamic_index_in_dim(c, layer, keepdims=False), -1, -2)
    return decode_attention(q, view(k_cache), view(v_cache), lengths)


def cache_write(k_cache: jnp.ndarray, v_cache: jnp.ndarray, k: jnp.ndarray,
                v: jnp.ndarray, lengths: jnp.ndarray, layer
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write row ``b``'s new token ``k[b]``, ``v[b]`` ((B, Hkv, D)) at
    position ``min(lengths[b], S - 1)`` of layer ``layer`` of stacked
    caches (L, B, Hkv, D, S); nothing else changes.  A select over the
    positions, so a sharded position axis stays sharded."""
    B, S = k_cache.shape[1], k_cache.shape[-1]
    pos = jnp.minimum(jnp.broadcast_to(lengths, (B,)), S - 1)
    hit = jnp.arange(S)[None, None, None, :] == pos[:, None, None, None]

    def put(cache, new):
        old = jax.lax.dynamic_index_in_dim(cache, layer, keepdims=False)
        upd = jnp.where(hit, new[..., None].astype(cache.dtype), old)
        return jax.lax.dynamic_update_index_in_dim(cache, upd, layer, 0)
    return put(k_cache, k), put(v_cache, v)


def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             B: jnp.ndarray, C: jnp.ndarray, chunk: int = 64,
             initial_state: Optional[jnp.ndarray] = None,
             return_state: bool = False):
    """Mamba-2 SSD (state-space duality) reference, chunked formulation.

    x:  (b, s, h, p)   inputs (already conv'd/activated)
    dt: (b, s, h)      positive step sizes (post softplus)
    A:  (h,)           negative state decay rates
    B:  (b, s, g, n)   input projections (g groups broadcast over h)
    C:  (b, s, g, n)   output projections
    Returns y: (b, s, h, p) [and final state (b, h, p, n)].

    Semantics: h_t = exp(dt_t*A) * h_{t-1} + dt_t * B_t x_t ; y_t = C_t h_t.
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    assert s % chunk == 0
    nc = s // chunk
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2) if rep > 1 else B  # (b, s, h, n)
    Ch = jnp.repeat(C, rep, axis=2) if rep > 1 else C

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = Bh.reshape(b, nc, chunk, h, n)
    Cc = Ch.reshape(b, nc, chunk, h, n)

    dA = dtc * A[None, None, None, :]              # (b, nc, L, h), negative
    dA_cs = jnp.cumsum(dA, axis=2)                 # inclusive cumsum
    # intra-chunk: y_intra[i] = sum_{j<=i} C_i . B_j x_j dt_j exp(cs_i-cs_j)
    seg = dA_cs[:, :, :, None, :] - dA_cs[:, :, None, :, :]  # (b,nc,i,j,h)
    iidx = jnp.arange(chunk)
    causal = iidx[:, None] >= iidx[None, :]
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cc, Bc)
    y_intra = jnp.einsum("bcijh,bcijh,bcjh,bcjhp->bcihp", cb, L, dtc, xc)

    # chunk-final states: S_c = sum_j exp(cs_L - cs_j) dt_j B_j x_j^T
    decay_to_end = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)      # (b,nc,L,h)
    states = jnp.einsum("bcjh,bcjh,bcjhn,bcjhp->bchpn",
                        decay_to_end, dtc, Bc, xc)

    # inter-chunk recurrence over c: S'_c = G_c S'_{c-1} + states_c
    G = jnp.exp(dA_cs[:, :, -1, :])                          # (b, nc, h)

    def scan_fn(carry, inp):
        g_c, st_c = inp
        new = g_c[:, :, None, None] * carry + st_c
        return new, carry  # emit the state *entering* this chunk

    # carry the inter-chunk state in fp32 regardless of activation dtype
    init = initial_state.astype(jnp.float32) if initial_state is not None \
        else jnp.zeros((b, h, p, n), jnp.float32)
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(G, 1, 0).astype(jnp.float32),
         jnp.moveaxis(states, 1, 0).astype(jnp.float32)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)            # (b,nc,h,p,n)

    # inter-chunk contribution: y_inter[i] = C_i exp(cs_i) S_prev
    decay_from_start = jnp.exp(dA_cs)                        # (b,nc,L,h)
    y_inter = jnp.einsum("bcihn,bcih,bchpn->bcihp",
                         Cc, decay_from_start, prev_states)

    y = (y_intra + y_inter).reshape(b, s, h, p).astype(x.dtype)
    if return_state:
        return y, final
    return y


def ssd_decode_step(state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
                    A: jnp.ndarray, B_t: jnp.ndarray, C_t: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-token SSD recurrence.  state: (b,h,p,n); x_t: (b,h,p);
    dt_t: (b,h); B_t, C_t: (b,g,n).  Returns (y_t, new_state)."""
    b, h, p = x_t.shape
    g = B_t.shape[1]
    rep = h // g
    Bh = jnp.repeat(B_t, rep, axis=1) if rep > 1 else B_t   # (b,h,n)
    Ch = jnp.repeat(C_t, rep, axis=1) if rep > 1 else C_t
    dA = jnp.exp(dt_t * A[None, :])                         # (b,h)
    new = dA[:, :, None, None] * state + \
        (dt_t[:, :, None] * x_t)[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    return y, new

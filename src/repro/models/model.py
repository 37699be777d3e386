"""End-to-end language models for all six assigned families.

Layers are stacked along a leading axis and consumed with ``lax.scan`` so
the compiled HLO is depth-independent (crucial for 40-cell × 2-mesh
dry-runs on one CPU).  Per-block remat keeps activation memory at
O(sqrt-ish) for training.  All sharding comes from the logical-axis rules.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingRules, constrain
from . import layers, params as P
from .config import ModelConfig

Params = Dict[str, Any]


def model_defs(cfg: ModelConfig):
    return P.model_defs(cfg)


def init_params(cfg: ModelConfig, key, dtype=None) -> Params:
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return P.init_params(P.model_defs(cfg), key, dtype)


def abstract_params(cfg: ModelConfig, dtype=None):
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return P.abstract_params(P.model_defs(cfg), dtype)


def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "dots":
        # selective remat: keep matmul outputs (the FLOPs that matter),
        # recompute elementwise/norm chains — near-zero re-forward FLOPs
        # for ~the activation memory of the dot outputs
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=True)
    if cfg.remat in ("block", "full"):
        return jax.checkpoint(fn, prevent_cse=True)
    return fn


def _cast(params: Params, cfg: ModelConfig):
    """Compute-dtype view of the params (bf16 matmuls, fp32 master)."""
    cdt = jnp.dtype(cfg.dtype)

    def leaf(x):
        return x.astype(cdt) if x.dtype == jnp.float32 and x.ndim >= 2 else x
    return jax.tree.map(leaf, params)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

@layers.scoped("embed")
def embed_tokens(params: Params, tokens, cfg: ModelConfig,
                 rules: ShardingRules):
    # T5-style sqrt(d) embedding scale: brings the residual stream to
    # O(1) at layer 0 so the pre-norm backward is depth-stable while the
    # tied unembedding keeps its 0.02-scale logits
    x = jnp.take(params["embed"], tokens, axis=0) \
        * jnp.asarray(math.sqrt(cfg.d_model), params["embed"].dtype)
    return constrain(x, rules, "batch", "act_seq", "d_model")


@layers.scoped("lm_head")
def lm_head(params: Params, x, cfg: ModelConfig, rules: ShardingRules):
    x = layers.norm(x, params["ln_f"], cfg)
    w = params["unembed"] if "unembed" in params else params["embed"].T
    logits = jnp.einsum("bsd,dv->bsv", x, w)
    # seq and vocab cannot both land on "model"; prefer the seq sharding
    # when sequence parallelism is on (CE is then fully token-parallel)
    if rules.act_seq is not None:
        logits = constrain(logits, rules, "batch", "act_seq", None)
    else:
        logits = constrain(logits, rules, "batch", "seq", "vocab")
    if cfg.padded_vocab != cfg.vocab:   # mask padded vocab rows
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e9)
    return logits


# ---------------------------------------------------------------------------
# backbones (mode: "train" | "prefill" | "decode")
# ---------------------------------------------------------------------------

def _scan_cached(block, x, layer_params, caches):
    """Run ``block(x, lp, cache) -> (x, aux, (k, v))`` over the layers with
    ``cache = (k, v, len, layer)``.  The stacked K and V are loop carries,
    never the scan's xs or ys: each layer reads and writes its part where
    it lies, and the donated cache is the same buffer from the step's
    entry to its exit."""
    def body(carry, inp):
        x, aux, k, v = carry
        lp, i = inp
        x, a, (k, v) = block(x, lp, (k, v, caches["len"], i))
        return (x, aux + a, k, v), None

    layer_ids = jnp.arange(caches["k"].shape[0], dtype=jnp.int32)
    (x, aux, k, v), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), caches["k"], caches["v"]),
        (layer_params, layer_ids))
    return x, aux, {"k": k, "v": v, "len": caches["len"] + x.shape[1]}


def _dense_backbone(params, x, cfg, rules, *, positions, caches, mode):
    if caches is not None:
        return _scan_cached(
            lambda x, lp, c: layers.attn_block(x, lp, cfg, rules,
                                               positions=positions, cache=c),
            x, params["layers"], caches)

    def body(carry, lp):
        x, aux = carry
        x, a, _ = layers.attn_block(x, lp, cfg, rules, positions=positions)
        return (x, aux + a), None

    body = _maybe_remat(body, cfg) if mode == "train" else body
    aux0 = jnp.zeros((), jnp.float32)
    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"])
    return x, aux, None


def _ssm_backbone(params, x, cfg, rules, *, caches, mode):
    def body(carry, inp):
        x = carry
        if caches is None:
            x, _ = layers.mamba_block(x, inp, cfg, rules)
            return x, None
        lp, lc = inp
        x, nc = layers.mamba_block(x, lp, cfg, rules, cache=lc)
        return x, nc

    body = _maybe_remat(body, cfg) if mode == "train" else body
    aux = jnp.zeros((), jnp.float32)
    if caches is None:
        x, _ = jax.lax.scan(body, x, params["layers"])
        return x, aux, None
    mc = (caches["conv_x"], caches["conv_B"], caches["conv_C"], caches["ssd"])
    x, new_mc = jax.lax.scan(body, x, (params["layers"], mc))
    return x, aux, {"conv_x": new_mc[0], "conv_B": new_mc[1],
                    "conv_C": new_mc[2], "ssd": new_mc[3],
                    "len": caches["len"] + x.shape[1]}


def _hybrid_backbone(params, x, cfg, rules, *, positions, caches, mode):
    """zamba2-style: stacked mamba blocks + ONE shared attention block
    (unstacked params) applied every ``attn_every`` layers."""
    every = cfg.attn_every
    shared = params["shared_attn"]

    def body(carry, inp):
        x, idx, attn_kv = carry
        if caches is None:
            lp = inp
            x, _ = layers.mamba_block(x, lp, cfg, rules)
        else:
            lp, lc = inp
            x, nc = layers.mamba_block(x, lp, cfg, rules, cache=lc)
        apply_attn = (idx + 1) % every == 0

        def with_attn(operand):
            x, attn_kv = operand
            app = (idx + 1) // every - 1
            if caches is None:
                y, a, _ = layers.attn_block(x, shared, cfg, rules,
                                            positions=positions)
                return y, attn_kv
            ck = jax.lax.dynamic_index_in_dim(attn_kv[0], app, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(attn_kv[1], app, keepdims=False)
            y, a, nc = layers.attn_block(
                x, shared, cfg, rules, positions=positions,
                cache=(ck, cv, caches["len"]))
            nk = jax.lax.dynamic_update_index_in_dim(attn_kv[0], nc[0], app, 0)
            nv = jax.lax.dynamic_update_index_in_dim(attn_kv[1], nc[1], app, 0)
            return y, (nk, nv)

        x, attn_kv = jax.lax.cond(apply_attn, with_attn,
                                  lambda op: op, (x, attn_kv))
        if caches is None:
            return (x, idx + 1, attn_kv), None
        return (x, idx + 1, attn_kv), nc

    body = _maybe_remat(body, cfg) if mode == "train" else body
    aux = jnp.zeros((), jnp.float32)
    if caches is None:
        (x, _, _), _ = jax.lax.scan(
            body, (x, jnp.int32(0), ()), params["layers"])
        return x, aux, None
    mc = (caches["conv_x"], caches["conv_B"], caches["conv_C"], caches["ssd"])
    (x, _, attn_kv), new_mc = jax.lax.scan(
        body, (x, jnp.int32(0), (caches["attn_k"], caches["attn_v"])),
        (params["layers"], mc))
    return x, aux, {"conv_x": new_mc[0], "conv_B": new_mc[1],
                    "conv_C": new_mc[2], "ssd": new_mc[3],
                    "attn_k": attn_kv[0], "attn_v": attn_kv[1],
                    "len": caches["len"] + x.shape[1]}


def _vlm_backbone(params, x, cfg, rules, *, positions, img_embeds, caches,
                  mode):
    """Grouped scan: [gated cross-attn to image tokens] then ``every``
    self-attn decoder layers, repeated n_groups times."""
    def group_body(carry, inp):
        x, aux = carry
        if caches is None:
            xp, sp = inp
        else:
            xp, sp, (gk, gv) = inp
        x = layers.cross_block(x, xp, cfg, rules, kv_x=img_embeds,
                               positions=positions)

        def inner(carry2, inp2):
            x, aux = carry2
            if caches is None:
                x, a, _ = layers.attn_block(x, inp2, cfg, rules,
                                            positions=positions)
                return (x, aux + a), None
            lp, (ck, cv) = inp2
            x, a, nc = layers.attn_block(
                x, lp, cfg, rules, positions=positions,
                cache=(ck, cv, caches["len"]))
            return (x, aux + a), (nc[0], nc[1])

        if caches is None:
            (x, aux), _ = jax.lax.scan(inner, (x, aux), sp)
            return (x, aux), None
        (x, aux), nkv = jax.lax.scan(inner, (x, aux), (sp, (gk, gv)))
        return (x, aux), nkv

    group_body = _maybe_remat(group_body, cfg) if mode == "train" \
        else group_body
    aux0 = jnp.zeros((), jnp.float32)
    if caches is None:
        (x, aux), _ = jax.lax.scan(group_body, (x, aux0),
                                   (params["cross"], params["layers"]))
        return x, aux, None
    (x, aux), new_kv = jax.lax.scan(
        group_body, (x, aux0),
        (params["cross"], params["layers"], (caches["k"], caches["v"])))
    return x, aux, {"k": new_kv[0], "v": new_kv[1],
                    "len": caches["len"] + x.shape[1]}


def _encode_audio(params, frames, cfg, rules):
    """Whisper encoder over (stubbed) precomputed frame embeddings."""
    x = frames + params["enc_pos_embed"][None, :frames.shape[1]]

    def body(x, lp):
        x, _, _ = layers.attn_block(x, lp, cfg, rules, positions=None,
                                    causal=False, use_rope=False)
        return x, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return layers.norm(x, params["ln_enc"], cfg)


def _encdec_backbone(params, x, cfg, rules, *, positions, enc_out, caches,
                     mode):
    def block(x, lp, cache=None):
        x, nc = layers.encdec_block(x, lp, cfg, rules, enc_out=enc_out,
                                    positions=positions, cache=cache)
        return x, jnp.zeros((), jnp.float32), nc

    if caches is not None:
        x, aux, nc = _scan_cached(block, x, params["layers"], caches)
        return x, aux, dict(nc, enc_out=enc_out)

    def body(x, lp):
        return block(x, lp)[0], None

    body = _maybe_remat(body, cfg) if mode == "train" else body
    x, _ = jax.lax.scan(body, x, params["layers"])
    return x, jnp.zeros((), jnp.float32), None


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def forward(params: Params, tokens, cfg: ModelConfig, rules: ShardingRules,
            *, aux_inputs: Optional[Dict] = None, caches=None,
            mode: str = "train", return_hidden: bool = False):
    """Returns (logits, moe_aux_loss, new_caches); with
    ``return_hidden`` the final-norm hidden states replace the logits
    (streaming-CE path computes the LM head itself)."""
    params = _cast(params, cfg)
    aux_inputs = aux_inputs or {}
    B, S = tokens.shape
    if caches is not None and mode == "decode":
        positions = jnp.broadcast_to(caches["len"][None, None], (B, S)) \
            if jnp.ndim(caches["len"]) == 0 else caches["len"][:, None]
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    x = embed_tokens(params, tokens, cfg, rules)
    if cfg.family == "encdec":
        x = x + params["pos_embed"][None, positions[0]] if B == 1 \
            else x + jnp.take(params["pos_embed"], positions, axis=0)

    fam = cfg.family
    if fam in ("dense", "moe"):
        x, aux, nc = _dense_backbone(params, x, cfg, rules,
                                     positions=positions, caches=caches,
                                     mode=mode)
    elif fam == "ssm":
        x, aux, nc = _ssm_backbone(params, x, cfg, rules, caches=caches,
                                   mode=mode)
    elif fam == "hybrid":
        x, aux, nc = _hybrid_backbone(params, x, cfg, rules,
                                      positions=positions, caches=caches,
                                      mode=mode)
    elif fam == "vlm":
        img = aux_inputs["img_embeds"].astype(x.dtype)
        x, aux, nc = _vlm_backbone(params, x, cfg, rules,
                                   positions=positions, img_embeds=img,
                                   caches=caches, mode=mode)
    elif fam == "encdec":
        if caches is not None and mode == "decode":
            enc_out = caches["enc_out"]
        else:
            enc_out = _encode_audio(params,
                                    aux_inputs["frames"].astype(x.dtype),
                                    cfg, rules)
        x, aux, nc = _encdec_backbone(params, x, cfg, rules,
                                      positions=positions, enc_out=enc_out,
                                      caches=caches, mode=mode)
    else:
        raise ValueError(fam)

    if return_hidden:
        return layers.norm(x, params["ln_f"], cfg), aux, nc
    logits = lm_head(params, x, cfg, rules)
    return logits, aux, nc


def loss_fn(params: Params, batch: Dict, cfg: ModelConfig,
            rules: ShardingRules, aux_weight: float = 0.01):
    aux_in = {k: v for k, v in batch.items()
              if k not in ("tokens", "targets")}
    if cfg.use_streaming_ce:
        # fused unembed + CE over vocab chunks: never materializes the
        # (B, S, V) logits (see blocked_ce.py)
        from .blocked_ce import streaming_ce
        hidden, aux, _ = forward(params, batch["tokens"], cfg, rules,
                                 aux_inputs=aux_in, mode="train",
                                 return_hidden=True)
        cparams = _cast(params, cfg)
        w = cparams["unembed"] if "unembed" in cparams             else cparams["embed"].T
        # largest divisor of the padded vocab <= ce_chunk
        V = cfg.padded_vocab
        chunk = min(cfg.ce_chunk, V)
        while V % chunk:
            chunk -= 1
        ce = streaming_ce(hidden, w, batch["targets"], cfg.vocab, chunk)
    else:
        logits, aux, _ = forward(params, batch["tokens"], cfg, rules,
                                 aux_inputs=aux_in, mode="train")
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, batch["targets"][..., None],
                                  axis=-1)[..., 0]
        ce = jnp.mean(logz - tgt)
    loss = ce + aux_weight * aux
    return loss, {"ce": ce, "aux": aux,
                  "ppl": jnp.exp(jnp.clip(ce, a_max=20.0))}


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16, abstract: bool = False):
    """Per-family cache pytree (stacked leading layer axis)."""
    L = cfg.n_layers

    def mk(shape, dt=dtype):
        if abstract:
            return jax.ShapeDtypeStruct(shape, dt)
        return jnp.zeros(shape, dt)

    fam = cfg.family
    out: Dict[str, Any] = {"len": mk((batch,), jnp.int32)}
    # self-attention K and V are stacked over the layers with the
    # positions minor, (L, B, KV, D, S): the layout a TPU gives them
    # anyway when head_dim is narrower than its 128 lanes, and the one the
    # decode step's kernels read and write in place (layers.attention)
    if fam in ("dense", "moe", "encdec"):
        kv = (L, batch, cfg.n_kv, cfg.hd, max_seq)
        out.update(k=mk(kv), v=mk(kv))
        if fam == "encdec":
            out["enc_out"] = mk((batch, cfg.enc_seq, cfg.d_model))
    elif fam == "vlm":
        every = cfg.cross_attn_every
        ngroups = L // every
        kv = (ngroups, every, batch, cfg.n_kv, max_seq, cfg.hd)
        out.update(k=mk(kv), v=mk(kv))
    elif fam in ("ssm", "hybrid"):
        W, inner = cfg.ssm_conv, cfg.ssm_inner
        GN = cfg.ssm_groups * cfg.ssm_state
        out.update(
            conv_x=mk((L, batch, W - 1, inner)),
            conv_B=mk((L, batch, W - 1, GN)),
            conv_C=mk((L, batch, W - 1, GN)),
            ssd=mk((L, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                    cfg.ssm_state), jnp.float32))
        if fam == "hybrid":
            napps = L // cfg.attn_every
            kv = (napps, batch, cfg.n_kv, max_seq, cfg.hd)
            out.update(attn_k=mk(kv), attn_v=mk(kv))
    return out


def cache_logical_axes(cfg: ModelConfig):
    """Logical axis names for every cache leaf (for shardings)."""
    fam = cfg.family
    out = {"len": (None,)}
    if fam in ("dense", "moe", "encdec"):
        kv = (None, "batch", "kv_heads", "head_dim", "cache_seq")
        out.update(k=kv, v=kv)
        if fam == "encdec":
            out["enc_out"] = ("batch", None, "d_model")
    elif fam == "vlm":
        kv = (None, None, "batch", "kv_heads", "cache_seq", "head_dim")
        out.update(k=kv, v=kv)
    elif fam in ("ssm", "hybrid"):
        out.update(conv_x=(None, "batch", None, "conv_dim"),
                   conv_B=(None, "batch", None, None),
                   conv_C=(None, "batch", None, None),
                   ssd=(None, "batch", "ssm_heads", None, None))
        if fam == "hybrid":
            kv = (None, "batch", "kv_heads", "cache_seq", "head_dim")
            out.update(attn_k=kv, attn_v=kv)
    return out

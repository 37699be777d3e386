"""Per-architecture smoke tests: every assigned arch instantiates its
REDUCED config and runs forward + loss + prefill/decode on CPU, asserting
shapes, finiteness, and decode-vs-teacher-forced consistency."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.distributed.sharding import BASELINE_RULES
from repro.models import (forward, loss_fn, init_params, init_caches,
                          cache_logical_axes, model_defs)
from repro.models.params import param_pspecs, count_params

B, S = 2, 32


def make_batch(cfg, rng):
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    if cfg.family == "vlm":
        batch["img_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_img_tokens, cfg.d_model)), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.enc_seq, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_forward_loss(arch):
    cfg = configs.get_smoke(arch)
    rng = np.random.default_rng(0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, rng)
    loss, metrics = jax.jit(lambda p, b: loss_fn(p, b, cfg,
                                                 BASELINE_RULES))(params,
                                                                  batch)
    assert np.isfinite(float(loss))
    assert float(loss) > 0
    logits, aux, _ = forward(params, batch["tokens"], cfg, BASELINE_RULES,
                             aux_inputs={k: v for k, v in batch.items()
                                         if k not in ("tokens", "targets")},
                             mode="train")
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert np.all(np.isfinite(np.asarray(logits, np.float32)))


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_smoke_prefill_decode_consistency(arch):
    """Greedy decode through the cache must match a teacher-forced full
    forward at the same position (bf16 tolerance)."""
    cfg = configs.get_smoke(arch)
    rng = np.random.default_rng(1)
    params = init_params(cfg, jax.random.PRNGKey(1))
    batch = make_batch(cfg, rng)
    aux = {k: v for k, v in batch.items() if k not in ("tokens", "targets")}

    caches = init_caches(cfg, B, S + 8)
    logits_p, _, caches = forward(params, batch["tokens"], cfg,
                                  BASELINE_RULES, aux_inputs=aux,
                                  caches=caches, mode="prefill")
    tok = jnp.argmax(logits_p[:, -1:], axis=-1).astype(jnp.int32)
    logits_d, _, caches = forward(params, tok, cfg, BASELINE_RULES,
                                  aux_inputs=aux, caches=caches,
                                  mode="decode")
    full = jnp.concatenate([batch["tokens"], tok], axis=1)
    logits_full, _, _ = forward(params, full, cfg, BASELINE_RULES,
                                aux_inputs=aux, mode="train")
    a = np.asarray(logits_d[:, 0], np.float32)
    b = np.asarray(logits_full[:, -1], np.float32)
    # compare normalized top-token agreement + logit closeness
    assert np.argmax(a, -1).tolist() == np.argmax(b, -1).tolist() or \
        np.max(np.abs(a - b)) < 0.25
    assert np.max(np.abs(a - b)) < 0.5


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_full_config_param_table(arch):
    """The FULL config's parameter table builds (no allocation) and every
    leaf has a consistent logical-spec entry."""
    cfg = configs.get_config(arch)
    defs = model_defs(cfg)
    n = count_params(defs)
    assert n > 1e8, f"{arch}: only {n} params"
    specs = param_pspecs(defs, BASELINE_RULES)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: x is None)
    assert leaves


@pytest.mark.parametrize("arch", ["mamba2-780m", "zamba2-7b"])
def test_ssm_archs_have_state_caches(arch):
    cfg = configs.get_smoke(arch)
    caches = init_caches(cfg, 2, 64)
    assert "ssd" in caches and "conv_x" in caches
    ax = cache_logical_axes(cfg)
    assert set(ax) == set(caches)


def test_moe_load_balance_aux_positive():
    cfg = configs.get_smoke("phi3.5-moe-42b-a6.6b")
    rng = np.random.default_rng(3)
    params = init_params(cfg, jax.random.PRNGKey(3))
    batch = make_batch(cfg, rng)
    _, metrics = loss_fn(params, batch, cfg, BASELINE_RULES)
    assert float(metrics["aux"]) >= 1.0 - 1e-3   # >= 1 by Cauchy-Schwarz


def test_streaming_ce_matches_standard():
    """Fused vocab-chunked CE (blocked_ce.py): loss identical, grads
    exact in f32 (in bf16 the STANDARD path loses precision via its
    logits-cast cotangent; streaming never materializes logits)."""
    import dataclasses
    base = configs.get_smoke("llama-3.2-vision-11b")
    cfg0 = dataclasses.replace(base, dtype="float32")
    cfg1 = dataclasses.replace(base, dtype="float32",
                               use_streaming_ce=True, ce_chunk=128)
    rng = np.random.default_rng(0)
    params = init_params(cfg0, jax.random.PRNGKey(0))
    batch = make_batch(cfg0, rng)
    (l0, _), g0 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg0, BASELINE_RULES),
        has_aux=True)(params)
    (l1, _), g1 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg1, BASELINE_RULES),
        has_aux=True)(params)
    assert float(l0) == pytest.approx(float(l1), abs=1e-5)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=1e-3)


@pytest.mark.parametrize("arch", ["smollm-135m", "granite-moe-3b-a800m"])
def test_served_decode_matches_teacher_forced(arch):
    """Serving's jitted steps (``step_functions``, through JaxExecutor):
    requests prefilled into separate slots at different times decode
    through the stacked cache with empty rows beside them, and each served
    token is the teacher-forced forward's best at its position (within
    bf16 rounding of the logits).  The moe config gets room for every
    token at every expert, so the teacher-forced pass drops none where a
    one-token decode step never does."""
    from repro.serving.executor import JaxExecutor

    cfg = configs.get_smoke(arch)
    if cfg.family == "moe":
        cfg = configs.get_smoke(arch, capacity_factor=float(cfg.n_experts))
    params = init_params(cfg, jax.random.PRNGKey(4))
    ex = JaxExecutor(cfg, params, BASELINE_RULES, batch_slots=5,
                     max_seq=128)
    rng = np.random.default_rng(4)
    prompts = {1: rng.integers(0, cfg.vocab, 5),
               3: rng.integers(0, cfg.vocab, 19)}
    late = {3: 2}                      # slot 3 joins after two steps
    state = ex.init_state()
    served = {slot: [] for slot in prompts}
    occupied = np.zeros(5, bool)
    tokens = np.zeros(5, np.int32)
    for step in range(6):
        for slot, p in prompts.items():
            if late.get(slot, 0) == step:
                frag, tok = ex.prefill(p, slot)
                state = ex.insert(state, frag, slot)
                served[slot].append(tok)
                occupied[slot], tokens[slot] = True, tok
        state, out = ex.decode(state, tokens, occupied)
        for slot in prompts:
            if occupied[slot]:
                served[slot].append(int(out[slot]))
                tokens[slot] = out[slot]
    lens = np.asarray(state["len"])
    assert lens[[0, 2, 4]].tolist() == [0, 0, 0]
    for slot, p in prompts.items():
        toks = served[slot]
        assert lens[slot] == len(p) + len(toks) - 1
        full = jnp.asarray(np.concatenate([p, toks[:-1]])[None], jnp.int32)
        logits, _, _ = forward(params, full, cfg, BASELINE_RULES,
                               mode="train")
        rows = np.asarray(logits[0, len(p) - 1:], np.float32)
        gap = rows.max(-1) - rows[np.arange(len(toks)), toks]
        assert gap.max() < 0.1, (slot, gap)

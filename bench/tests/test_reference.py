"""The plain references agree with the program at smoke size on the CPU."""

from __future__ import annotations

import numpy as np
import pytest

from bench import harness, weights
from bench.reference import dense_lm
from bench.tests.smoke import REPO, SMOKE_LM


def _program_logits(cfg, params, tokens, compute):
    """The program's own forward pass (``repro.models.forward``) over one
    sequence, computing in ``compute``."""
    import jax.numpy as jnp
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import forward

    driver = harness.load_module(REPO / "bench/drivers/serve_openloop.py",
                                 "serving driver")
    mcfg = driver.model_config(
        dict(cfg, dtypes=dict(cfg["dtypes"], compute=compute)))
    logits, _, _ = forward(params, jnp.asarray(tokens)[None], mcfg,
                           BASELINE_RULES, mode="prefill")
    return np.asarray(logits[0], np.float32)


@pytest.mark.parametrize("seed", [5, 9])
def test_reference_logits_equal_program_logits(seed):
    """Same weights and tokens: the program's forward in float32 and the
    reference agree to float32 rounding, so the reference computes the
    program's model and not a neighbour of it."""
    cfg = SMOKE_LM
    params = weights.dense_lm(cfg, seed=seed)
    toks = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], 33).astype(np.int32)
    got = _program_logits(cfg, params, toks, "float32")
    want = dense_lm.reference_logits(params, cfg, toks)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)


def test_served_gaps_read_the_right_positions():
    """A served stream equal to the reference's own argmax reads 0; one
    token changed reads that token's gap at its position (the positions
    before it are untouched)."""
    cfg = SMOKE_LM
    params = weights.dense_lm(cfg, seed=3)
    prompt = np.arange(1, 11, dtype=np.int32)
    toks = list(prompt)
    for _ in range(12):
        toks.append(int(np.argmax(
            dense_lm.reference_logits(params, cfg, toks)[-1])))
    served = np.asarray(toks[10:], np.int32)
    assert float(np.max(dense_lm.served_gaps(
        params, cfg, prompt, served, 64)["gaps"])) == 0.0
    logits = dense_lm.reference_logits(params, cfg, toks[:15])
    wrong = served.copy()
    wrong[5] = int(np.argmin(logits[14]))
    g = dense_lm.served_gaps(params, cfg, prompt, wrong, 64)["gaps"]
    assert g[5] == pytest.approx(logits[14].max() - logits[14].min(),
                                 rel=1e-5)
    assert np.all(g[:5] == 0.0)

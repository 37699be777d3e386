"""The control, on the CPU at a size a test run holds: the reference
computed in the precision below the configuration's must read above the
cell's limit, and the program below it.

The chip runs the same comparison at the cells' own sizes:
``python3 bench/control.py --workload <cell> --seeds ...``.

Full width (576 wide, 49,152-token vocabulary), 8 of the 30 layers, 128
positions of random tokens: the program's bfloat16 forward picks a token
at each position, the float8 control picks one, and each is read by its
gap below the float32 reference's best logit.  At the smoke size of the
serving tests float8 never changes a token, so this test keeps the
widths.  It is a prefill over random tokens, not the served path; the
limit is set from the chip's readings of the served path (PERF.md).
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, weights
from bench.reference import dense_lm
from bench.tests.smoke import REPO


def _limit(cell: str, name: str) -> float:
    wl = json.loads((REPO / f"bench/workloads/{cell}.json").read_text())
    return float(wl["check"]["limits"][name])


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_dense_float8_control_fails_the_limit(seed):
    import jax
    import jax.numpy as jnp
    from repro.distributed.sharding import BASELINE_RULES
    from repro.models import forward

    base = json.loads((REPO / "bench/configs/smollm-135m.json").read_text())
    cfg = dict(base, num_hidden_layers=8)
    driver = harness.load_module(REPO / "bench/drivers/serve_openloop.py",
                                 "serving driver")
    mcfg = driver.model_config(cfg)
    params = weights.dense_lm(cfg, seed)
    S = 128
    toks = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], S).astype(np.int32)
    program = jax.jit(lambda p, t: forward(
        p, t[None], mcfg, BASELINE_RULES, mode="prefill")[0][0])
    prog_top = np.asarray(program(params, jnp.asarray(toks))
                          .astype(jnp.float32)).argmax(-1)
    ref = dense_lm.reference_logits(params, cfg, toks)
    fp8 = dense_lm._forward_fn(dense_lm._dims(cfg), True)[1]
    ctrl_top = np.asarray(fp8(params, jnp.asarray(toks))).argmax(-1)

    def widest_gap(top):
        return float(np.max(ref.max(-1) - ref[np.arange(S), top]))

    limit = _limit("smollm-135m.chat-steady", "max_logit_gap")
    assert widest_gap(prog_top) < limit
    assert widest_gap(ctrl_top) > limit

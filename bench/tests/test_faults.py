"""A run whose timed path is broken underneath comes out ``correct: false``.

Each test drives a whole smoke-size run through ``bench/run.py`` on the
CPU (the chip check skipped) with one fault planted in the program:

* a step that returns its state unchanged;
* half of the batch left out;
* a token altered where it is produced.

The cell runs on one chip, so there is no exchange between chips to
leave out.  A sound run of the same cell passes, so each failure is the
fault's.  A last test runs the cell with the control judged in the
program's place, as ``bench/control.py`` does on the chip.
"""

from __future__ import annotations

import numpy as np
import pytest

from bench.tests import smoke

CHAT = "smollm-smoke.smoke-chat"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return smoke.make_tree(tmp_path_factory.mktemp("tree"))


def _correct(tree, cell, seed=7):
    rc, result, out, err = smoke.run_cell(tree, cell, seed=seed,
                                          seconds=1.5)
    assert rc == 0, err[-3000:]
    assert list(result)[-1] == "checks"
    return result["correct"]


def test_sound_run_is_correct(tree):
    assert _correct(tree, CHAT)


# -- serving: JaxExecutor.decode ----------------------------------------------

def _patch_decode(monkeypatch, wrap):
    from repro.serving.executor import JaxExecutor
    real = JaxExecutor.decode
    monkeypatch.setattr(JaxExecutor, "decode",
                        lambda self, st, toks, occ: wrap(real, self, st,
                                                         toks, occ))


def test_decode_altered_token(tree, monkeypatch):
    def wrap(real, self, st, toks, occ):
        st, out = real(self, st, toks, occ)
        return st, (np.asarray(out) + 1) % self.cfg.vocab
    _patch_decode(monkeypatch, wrap)
    assert not _correct(tree, CHAT)


def test_decode_returns_state_unchanged(tree, monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(real, self, st, toks, occ):
        kept = jax.tree.map(jnp.copy, st)     # the real step donates st
        _, out = real(self, st, toks, occ)
        return kept, out
    _patch_decode(monkeypatch, wrap)
    assert not _correct(tree, CHAT)


def test_decode_leaves_out_half_the_batch(tree, monkeypatch):
    def wrap(real, self, st, toks, occ):
        half = np.asarray(occ).copy()
        half[np.flatnonzero(half)[::2]] = False   # every other busy row
        return real(self, st, toks, half)
    _patch_decode(monkeypatch, wrap)
    assert not _correct(tree, CHAT)


# -- the control, judged through the harness ------------------------------------

def test_control_run_is_not_correct(tree, monkeypatch):
    """In control mode the check holds the control's tokens to the limit.
    At smoke size float8 flips no token, so a coarser rounding (two
    significant bits) stands in for it here; the program's own reading
    of the same requests still passes."""
    import jax.numpy as jnp
    from bench import harness, run
    from bench.reference import dense_lm

    def coarse(x):
        e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 1e-30)))
        return jnp.round(x / 2.0 ** (e - 1)) * 2.0 ** (e - 1)
    monkeypatch.setattr(dense_lm, "_quant8", coarse)
    dense_lm._forward_fn.cache_clear()
    cell = harness.load_cell(CHAT, tree)
    ctx = run.RunContext(cell, run.parse(
        ["--workload", CHAT, "--seed", "7", "--seconds", "1.5"]),
        harness, harness.require_chips(1, allow_cpu=True))
    ctx.control = True
    try:
        out = harness.load_driver(cell).run(ctx)
    finally:
        dense_lm._forward_fn.cache_clear()
    limit = out.checks[0].limit
    assert max(out.obs["gaps"]) <= limit
    assert out.checks[0].value == max(out.obs["control_gaps"]) > limit
    assert not out.correct

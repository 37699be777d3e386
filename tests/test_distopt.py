"""Distributed-optimization tricks: gradient compression, hierarchical
collectives, straggler monitor, elastic re-mesh planner."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.training.compression import (
    compress, decompress, compress_grads, decompress_grads)
from repro.training.straggler import (StragglerMonitor, StragglerConfig,
                                      plan_elastic_mesh)
from repro.distributed.collectives import hierarchical_psum


def test_compress_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    q, s = compress(g)
    assert q.dtype == jnp.int8
    err = jnp.abs(decompress(q, s) - g)
    assert float(err.max()) <= float(s) / 2 + 1e-7


def test_error_feedback_reduces_bias():
    """With feedback, the accumulated reconstruction over many steps
    tracks the accumulated true gradient (bias -> 0)."""
    rng = np.random.default_rng(1)
    true_sum = np.zeros(64, np.float32)
    recon_sum = np.zeros(64, np.float32)
    grads = {"w": None}
    fb = {"w": jnp.zeros(64, jnp.float32)}
    for step in range(50):
        g = jnp.asarray(rng.normal(size=64) * 0.01, jnp.float32)
        true_sum += np.asarray(g)
        qtree, fb = compress_grads({"w": g}, fb)
        recon = decompress_grads(qtree)
        recon_sum += np.asarray(recon["w"])
    # the residual never exceeds one quantization step (feedback carries it)
    assert np.abs(true_sum - recon_sum).max() < 0.01


def test_compression_ratio():
    g = jnp.ones((1024,), jnp.float32)
    q, s = compress(g)
    assert q.nbytes * 4 == g.nbytes    # 4x fewer bytes than f32


def test_hierarchical_psum_matches_flat():
    """On a 1x1 (pod-less) host mesh the wrapper reduces over 'data'."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",))
    x = jnp.arange(8.0)

    f = jax.shard_map(lambda t: hierarchical_psum(t, mesh), mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_straggler_monitor_flags_persistent_outlier():
    mon = StragglerMonitor(StragglerConfig(window=10, slow_factor=1.5,
                                           persist_steps=3))
    for step in range(6):
        for h in ("host0", "host1", "host2", "host3"):
            mon.record(h, 1.0)
        mon.record("host4", 3.0)        # persistent straggler
        flagged = mon.check()
    assert flagged == ["host4"]


def test_straggler_transient_not_flagged():
    mon = StragglerMonitor(StragglerConfig(persist_steps=3))
    for step in range(6):
        for h in ("a", "b", "c", "d"):
            mon.record(h, 1.0)
        mon.record("e", 3.0 if step == 2 else 1.0)   # one-off blip
        assert mon.check() == []


def test_elastic_mesh_plan():
    assert plan_elastic_mesh(512) == (2, 16, 16)
    assert plan_elastic_mesh(511) == (1, 16, 16)     # lost a chip -> 1 pod
    assert plan_elastic_mesh(256) == (1, 16, 16)
    assert plan_elastic_mesh(255) == (1, 8, 16)
    assert plan_elastic_mesh(16) == (1, 1, 16)
    assert plan_elastic_mesh(15) is None


def test_elastic_plan_keeps_model_axis():
    for chips in (512, 400, 300, 256, 128, 64):
        plan = plan_elastic_mesh(chips)
        assert plan is not None and plan[2] == 16
        assert plan[0] * plan[1] * plan[2] <= chips

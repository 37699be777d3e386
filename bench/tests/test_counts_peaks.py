"""Peaks table, needed-work counts, percentiles and the traffic generator.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import harness, peaks, traffic
from bench.counts import dense_lm
from bench.tests.smoke import REPO

SMOLLM = json.loads((REPO / "bench/configs/smollm-135m.json").read_text())


def test_unknown_device_kind_fails():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v99 imaginary")
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_percentile_is_over_all_samples():
    xs = list(range(1, 101))            # 1..100
    assert harness.percentile(xs, 95) == pytest.approx(
        float(np.percentile(xs, 95)))
    assert harness.percentile(xs, 100) == 100.0
    assert harness.percentile([3.0], 95) == 3.0
    # one slow sample out of twenty moves the 95th percentile: nothing
    # is thinned or dropped
    fast = [1.0] * 19
    assert harness.percentile(fast + [1000.0], 95) > \
        harness.percentile(fast + [1.0], 95)
    with pytest.raises(ValueError):
        harness.percentile([], 95)


def test_smollm_counts_match_parameter_arithmetic():
    d, ff, L, V = 576, 1536, 30, 49152
    per_layer = d * 64 * (2 * 9 + 2 * 3) + 3 * d * ff
    assert dense_lm.layer_matmul_params(SMOLLM) == per_layer
    assert dense_lm.head_params(SMOLLM) == d * V
    # ~134.5M multiply-adds per decoded token before attention
    base = 2.0 * (L * per_layer + d * V)
    assert dense_lm.decode_token_flops(SMOLLM, 0) == \
        pytest.approx(base + 4.0 * L * 9 * 64)
    # attention grows with the live context, not the cache's capacity
    assert dense_lm.decode_token_flops(SMOLLM, 1023) - \
        dense_lm.decode_token_flops(SMOLLM, 0) == \
        pytest.approx(4.0 * L * 9 * 64 * 1023)


def test_smollm_cache_bytes():
    per_pos = 2.0 * 2 * 30 * 3 * 64                  # bf16 keys + values
    assert dense_lm.kv_bytes(SMOLLM, 1) == per_pos
    # the engine's 64 x 2048 cache is the 3.0 GB PERF.md states
    assert dense_lm.kv_bytes(SMOLLM, 64 * 2048) == pytest.approx(3.02e9,
                                                                  rel=1e-3)


def test_every_seed_offers_the_same_work():
    mix = json.loads((REPO / "bench/workloads/smollm-135m.chat-steady.json")
                     .read_text())["mix"]
    a = traffic.open_loop(mix, 30.0, 10.0, 49152, seed=1)
    b = traffic.open_loop(mix, 30.0, 10.0, 49152, seed=2 ** 31 + 12345)
    for block in ("preroll", "window", "after"):
        ra = [r for r in a if r.block == block]
        rb = [r for r in b if r.block == block]
        assert sorted(len(r.prompt) for r in ra) == \
            sorted(len(r.prompt) for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
        assert [r.due for r in ra] != [r.due for r in rb]
    win = [r for r in a if r.block == "window"]
    assert len(win) == 300
    assert 0.0 <= min(r.due for r in win) and max(r.due for r in win) < 10.0
    plens = [len(r.prompt) for r in a]
    assert min(plens) >= 16 and max(plens) <= 1536
    assert 950 <= np.median(plens) <= 1090
    outs = [r.max_new for r in a]
    assert min(outs) >= 16 and max(outs) <= 512
    # prompt + output always fits the 2048-position cache
    assert max(len(r.prompt) + r.max_new for r in a) <= 2048


def test_warm_buckets_are_the_buckets_the_traffic_uses():
    """Set-up warms exactly the prefill buckets (the executor's rule: a
    power of two, at least ``prefill_bucket``) of a full run's prompts."""
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    wl = json.loads((REPO / "bench/workloads/smollm-135m.chat-steady.json")
                    .read_text())
    srv = SMOLLM["serving"]
    arrivals = traffic.open_loop(wl["mix"], wl["mix"]["rate_rps"],
                                 spec["run_seconds"], 49152, seed=3)
    used = {min(max(srv["prefill_bucket"],
                    1 << (len(a.prompt) - 1).bit_length()), srv["max_seq"])
            for a in arrivals}
    assert used == set(wl["warm_buckets"])


def test_lengths_and_gaps_follow_their_distributions():
    u = traffic.lengths({"dist": "uniform", "min": 8, "max": 64}, 5700)
    assert u.min() == 8 and u.max() == 64
    assert abs(u.mean() - 36.0) < 0.1
    ln = traffic.lengths({"dist": "lognormal", "median": 1024, "sigma": 0.4,
                          "min": 512, "max": 1984}, 1001)
    assert ln[500] == 1024
    gaps = traffic.poisson_gaps(50.0, 20000)
    assert abs(gaps.mean() - 1 / 50.0) < 1e-3
    assert abs(np.median(gaps) - np.log(2) / 50.0) < 1e-4

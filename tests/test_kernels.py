"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs the ref.py
pure-jnp oracle (interpret=True executes the kernel body on CPU)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import kv_cache, ops, ref


def rnd(rng, shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.normal(size=shape) * scale).astype(dtype)


ATTN_SHAPES = [
    # B, S, H, KV, D, causal
    (1, 128, 4, 4, 64, True),
    (2, 128, 4, 2, 64, True),
    (2, 256, 8, 1, 64, True),
    (1, 256, 4, 4, 128, False),
]


@pytest.mark.parametrize("B,S,H,KV,D,causal", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(B, S, H, KV, D, causal, dtype):
    rng = np.random.default_rng(0)
    q = rnd(rng, (B, S, H, D), dtype)
    k = rnd(rng, (B, S, KV, D), dtype)
    v = rnd(rng, (B, S, KV, D), dtype)
    out = ops.attention(q, k, v, causal=causal, use_pallas=True,
                        block_q=128, block_k=128)
    want = ref.attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


DECODE_SHAPES = [
    (1, 4, 4, 64, 256),
    (2, 8, 2, 64, 512),
    (4, 8, 1, 128, 256),
]


@pytest.mark.parametrize("B,H,KV,D,S", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(B, H, KV, D, S, dtype):
    rng = np.random.default_rng(1)
    q = rnd(rng, (B, H, D), dtype)
    kc = rnd(rng, (B, KV, S, D), dtype)
    vc = rnd(rng, (B, KV, S, D), dtype)
    lengths = jnp.asarray(rng.integers(1, S, (B,)), jnp.int32)
    out = ops.decode_attention(q, kc, vc, lengths, use_pallas=True)
    want = ref.decode_attention(q, kc, vc, lengths)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


#: the stacked cache of the decode step: 3 layers x 8 rows, (L, B, KV, D, S)
STACK_L, STACK_S = 3, 256
#: rows 0-4 at the edge lengths; rows 5-7 unoccupied (the decode step pins
#: an empty row's length to 0)
ROW_LENGTHS = [0, 1, 127, 128, STACK_S - 1, 0, 0, 0]


def _stack(rng, kv_heads, head_dim):
    shape = (STACK_L, len(ROW_LENGTHS), kv_heads, head_dim, STACK_S)
    return rnd(rng, shape, jnp.bfloat16), rnd(rng, shape, jnp.bfloat16)


@pytest.mark.parametrize("layer", [0, 1, STACK_L - 1])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_cache_write_kernel(layer, head_dim):
    """Each row's token lands at its own length in the one layer, bit for
    bit, and no other element of either stack changes."""
    rng = np.random.default_rng(6)
    KV, B = 2, len(ROW_LENGTHS)
    kc, vc = _stack(rng, KV, head_dim)
    kn = rnd(rng, (B, KV, head_dim), jnp.bfloat16)
    vn = rnd(rng, (B, KV, head_dim), jnp.bfloat16)
    lengths = jnp.asarray(ROW_LENGTHS, jnp.int32)
    got = kv_cache.cache_write(kc, vc, kn, vn, lengths, layer,
                               interpret=True)
    oracle = ref.cache_write(kc, vc, kn, vn, lengths, layer)
    for cache, new, out, want in zip((kc, vc), (kn, vn), got, oracle):
        expect = np.array(cache)
        for b, n in enumerate(ROW_LENGTHS):
            expect[layer, b, :, :, n] = np.asarray(new[b])
        np.testing.assert_array_equal(np.asarray(out), expect)
        np.testing.assert_array_equal(np.asarray(want), expect)


@pytest.mark.parametrize("layer", [0, 1, STACK_L - 1])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("block_k", [None, 128])
def test_layer_decode_attention_kernel(layer, head_dim, block_k):
    """Attention over one layer of the stack, read in place, against the
    oracle on that layer's D-minor view, (B, KV, S, D).  The decode step
    attends over each row's length plus its new token."""
    rng = np.random.default_rng(7)
    KV, G, B = 2, 3, len(ROW_LENGTHS)
    kc, vc = _stack(rng, KV, head_dim)
    q = rnd(rng, (B, KV * G, head_dim), jnp.bfloat16)
    lengths = jnp.asarray(ROW_LENGTHS, jnp.int32) + 1
    out = kv_cache.decode_attention(q, kc, vc, lengths, layer,
                                    block_k=block_k, interpret=True)
    want = ref.decode_attention(q, jnp.swapaxes(kc[layer], -1, -2),
                                jnp.swapaxes(vc[layer], -1, -2), lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("rows,d", [(8, 256), (16, 512), (4, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(rows, d, dtype):
    rng = np.random.default_rng(2)
    x = rnd(rng, (rows, d), dtype)
    w = rnd(rng, (d,), jnp.float32)
    out = ops.rmsnorm(x, w, use_pallas=True)
    want = ref.rmsnorm(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


SSD_SHAPES = [
    (1, 128, 4, 64, 16, 64),
    (2, 256, 8, 32, 32, 64),
    (1, 64, 2, 64, 64, 32),
]


@pytest.mark.parametrize("B,L,H,P,N,chunk", SSD_SHAPES)
def test_ssd_scan_kernel(B, L, H, P, N, chunk):
    rng = np.random.default_rng(3)
    x = rnd(rng, (B, L, H, P), scale=0.1)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, (H,)), jnp.float32)
    Bm = rnd(rng, (B, L, 1, N), scale=0.1)
    Cm = rnd(rng, (B, L, 1, N), scale=0.1)
    y1, s1 = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, use_pallas=True)
    y2, s2 = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, return_state=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4, rtol=1e-4)


def test_ssd_scan_matches_sequential_recurrence():
    """The chunked SSD formulation equals the literal per-step recurrence."""
    rng = np.random.default_rng(4)
    B, L, H, P, N = 1, 32, 2, 8, 4
    x = rnd(rng, (B, L, H, P), scale=0.3)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, (H,)), jnp.float32)
    Bm = rnd(rng, (B, L, 1, N), scale=0.3)
    Cm = rnd(rng, (B, L, 1, N), scale=0.3)
    y_chunk = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=8)

    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(L):
        y_t, state = ref.ssd_decode_step(state, x[:, t], dt[:, t], A,
                                         Bm[:, t], Cm[:, t])
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-4)


def test_ssd_initial_state_continuation():
    """Splitting a sequence in half and carrying the state must equal one
    pass over the full sequence (prefill->decode handoff invariant)."""
    rng = np.random.default_rng(5)
    B, L, H, P, N = 1, 64, 2, 16, 8
    x = rnd(rng, (B, L, H, P), scale=0.2)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (B, L, H)), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 1.5, (H,)), jnp.float32)
    Bm = rnd(rng, (B, L, 1, N), scale=0.2)
    Cm = rnd(rng, (B, L, 1, N), scale=0.2)
    y_full, s_full = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=16,
                                  return_state=True)
    half = L // 2
    y1, s1 = ref.ssd_scan(x[:, :half], dt[:, :half], A, Bm[:, :half],
                          Cm[:, :half], chunk=16, return_state=True)
    y2, s2 = ref.ssd_scan(x[:, half:], dt[:, half:], A, Bm[:, half:],
                          Cm[:, half:], chunk=16, initial_state=s1,
                          return_state=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               atol=1e-4, rtol=1e-4)

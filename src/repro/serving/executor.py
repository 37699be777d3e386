"""Batch executors: the device-facing half of the serving engine.

The continuous-batching scheduler (:mod:`repro.serving.engine`) is pure
host logic — slots, paged KV accounting, admission, preemption.  All
model work goes through a small executor interface so the scheduler can
be driven by the real jitted model or by a cheap deterministic stub (the
property-test harness steps the scheduler thousands of times; tracing a
real model for that would hide scheduler bugs behind jit latency):

* ``init_state()``                  — the batch-wide decode state
  (one row per slot; rows are independent).
* ``prefill(prompt, slot)``         — run one request's prompt in
  isolation (batch 1), returning a single-row state fragment plus the
  first sampled token.  Never touches the batch state, so the DAG can
  overlap it with a decode step.
* ``insert(state, fragment, slot)`` — splice a fragment into a slot row.
* ``decode(state, tokens, occupied)`` — one synchronized token for every
  occupied slot.  Row ``i`` of the result depends only on row ``i`` of
  the state, which is what makes per-request outputs independent of how
  requests were interleaved into slots (tests/test_serving_props.py).
* ``cache_bytes(batch, seq)``       — KV footprint, for page sizing.

:class:`JaxExecutor` is the production implementation over
``repro.models.forward``; :class:`StubExecutor` is the deterministic
pure-numpy one used by the scheduler property harness and the
fault-injection tests.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Tuple

import numpy as np


class BatchExecutor:
    """Interface contract (see module docstring).  Subclasses must set
    ``batch_slots`` and ``max_seq``."""

    batch_slots: int
    max_seq: int

    def init_state(self) -> Any:
        raise NotImplementedError

    def prefill(self, prompt: np.ndarray, slot: int) -> Tuple[Any, int]:
        raise NotImplementedError

    def insert(self, state: Any, fragment: Any, slot: int) -> Any:
        raise NotImplementedError

    def decode(self, state: Any, tokens: np.ndarray,
               occupied: np.ndarray) -> Tuple[Any, np.ndarray]:
        raise NotImplementedError

    def cache_bytes(self, batch: int, seq: int) -> int:
        raise NotImplementedError

    def compile_stats(self) -> Dict[str, int]:
        return {}


# ---------------------------------------------------------------------------
# production executor over the jitted model
# ---------------------------------------------------------------------------

def step_functions(cfg, rules, aux: Dict[str, np.ndarray]):
    """The jitted ``(prefill, insert, decode)`` steps of
    :class:`JaxExecutor` over ``repro.models.forward``; each donates the
    cache it updates.  Module-level so a compile rehearsal can lower them
    for a described chip without placing any weights."""
    import jax
    import jax.numpy as jnp

    from repro.models import cache_logical_axes, forward

    axes = cache_logical_axes(cfg)

    def batch_axis(key: str) -> int:
        ax = axes.get(key)
        if ax and "batch" in ax:
            return ax.index("batch")
        return 0          # "len" and any unannotated leaf: axis 0

    def prefill_fn(params, toks, caches, last_idx, true_len, slot):
        aux_row = {k: jax.lax.dynamic_slice_in_dim(jnp.asarray(v), slot, 1,
                                                   axis=0)
                   for k, v in aux.items()}
        logits, _, caches = forward(params, toks, cfg, rules,
                                    aux_inputs=aux_row, caches=caches,
                                    mode="prefill")
        tok = jnp.argmax(logits[0, last_idx]).astype(jnp.int32)
        caches = dict(caches)
        caches["len"] = jnp.full_like(caches["len"], true_len)
        return tok, caches

    def insert_fn(state, frag, slot):
        out = {}
        for key, leaf in state.items():
            start = [0] * leaf.ndim
            start[batch_axis(key)] = slot
            out[key] = jax.lax.dynamic_update_slice(
                leaf, frag[key].astype(leaf.dtype), tuple(start))
        return out

    def decode_fn(params, toks, caches, occupied):
        logits, _, caches = forward(params, toks, cfg, rules,
                                    aux_inputs=aux, caches=caches,
                                    mode="decode")
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        caches = dict(caches)
        caches["len"] = jnp.where(occupied, caches["len"], 0)
        return tok, caches

    return (jax.jit(prefill_fn, donate_argnums=(2,)),
            jax.jit(insert_fn, donate_argnums=(0,)),
            jax.jit(decode_fn, donate_argnums=(2,)))


class JaxExecutor(BatchExecutor):
    """Jitted prefill / insert / decode over ``repro.models.forward``
    (:func:`step_functions`), placed on one JAX device.

    Three jitted functions, each compiled once per shape:

    * prefill: batch-1, prompt padded to a power-of-two bucket (floor
      ``prefill_bucket``) so mixed prompt lengths hit a handful of
      shapes instead of one compile per length.  Padding is exact: the
      prompt is left-aligned, the first token is read at the *true* last
      position, and the cache length is overridden to the true length,
      so junk K/V beyond it is masked out (and overwritten by decode).
    * insert: splices a batch-1 cache pytree into one row of the batch
      cache, ``dynamic_update_slice`` along each leaf's batch axis
      (from :func:`repro.models.cache_logical_axes`).
    * decode: one token for the whole batch; empty slots are masked —
      their cache length is pinned to 0 so they never grow or attend.

    ``device`` (a JAX device, default ``jax.devices()[0]``) holds the
    params and every cache, so every step runs there.
    """

    def __init__(self, cfg, params, rules, batch_slots: int, max_seq: int,
                 aux_inputs: Optional[Dict] = None, prefill_bucket: int = 8,
                 device=None):
        import jax

        from repro.models import init_caches

        self.device = device if device is not None else jax.devices()[0]
        self.cfg, self.rules = cfg, rules
        self.params = jax.device_put(params, self.device)
        self.batch_slots, self.max_seq = batch_slots, max_seq
        self.aux = {k: np.asarray(v) for k, v in (aux_inputs or {}).items()}
        self.prefill_bucket = max(1, prefill_bucket)
        self._init_caches = init_caches
        self._jax = jax
        self._prefill, self._insert, self._decode = step_functions(
            cfg, rules, self.aux)
        self._calls = {"prefill": 0, "decode": 0, "insert": 0}
        self._lock = threading.Lock()

    def _caches(self, batch: int):
        """Zeroed caches for ``batch`` rows, created on the device."""
        with self._jax.default_device(self.device):
            caches = self._init_caches(self.cfg, batch, self.max_seq)
        return self._jax.device_put(caches, self.device)

    def _put(self, x):
        return self._jax.device_put(x, self.device)

    # -- interface -------------------------------------------------------------
    def init_state(self):
        return self._caches(self.batch_slots)

    def bucket(self, prompt_len: int) -> int:
        """Padded prefill length for a prompt (pow2, floored, capped)."""
        b = max(self.prefill_bucket, 1 << (max(1, prompt_len) - 1)
                .bit_length())
        return min(b, self.max_seq)

    def prefill(self, prompt: np.ndarray, slot: int):
        plen = int(len(prompt))
        padded = self.bucket(plen)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :plen] = prompt
        with self._lock:
            self._calls["prefill"] += 1
        tok, frag = self._prefill(self.params, self._put(toks),
                                  self._caches(1), np.int32(plen - 1),
                                  np.int32(plen), np.int32(slot))
        return frag, int(tok)

    def insert(self, state, fragment, slot: int):
        with self._lock:
            self._calls["insert"] += 1
        return self._insert(state, fragment, np.int32(slot))

    def decode(self, state, tokens: np.ndarray, occupied: np.ndarray):
        with self._lock:
            self._calls["decode"] += 1
        tok, state = self._decode(
            self.params, self._put(np.asarray(tokens, np.int32)[:, None]),
            state, self._put(np.asarray(occupied)))
        return state, np.asarray(tok)

    def cache_bytes(self, batch: int, seq: int) -> int:
        import jax.tree_util as jtu
        abstract = self._init_caches(self.cfg, batch, seq, abstract=True)
        return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                       for leaf in jtu.tree_leaves(abstract)))

    # -- bookkeeping -----------------------------------------------------------
    def compile_stats(self) -> Dict[str, int]:
        """Call and (re)compile counters proving steady-state serving does
        zero tracing work (docs/caching.md §Steady-state serving)."""
        with self._lock:
            calls = dict(self._calls)
        return {
            "prefill_calls": calls["prefill"],
            "decode_steps": calls["decode"],
            "insert_calls": calls["insert"],
            "prefill_compiles": self._prefill._cache_size(),
            "decode_compiles": self._decode._cache_size(),
            "insert_compiles": self._insert._cache_size(),
        }


# ---------------------------------------------------------------------------
# deterministic stub executor (property harness / fault injection)
# ---------------------------------------------------------------------------

class StubExecutor(BatchExecutor):
    """Pure-numpy deterministic executor.

    Token ``j`` of a request is a hash of (prompt, prompt length, j) —
    nothing else — so the expected output stream of any request is
    computable up front (:meth:`expected_tokens`) and *must* be
    independent of slot assignment, co-tenants, preemption, and arrival
    order.  The scheduler property harness leans on exactly that.

    ``delay_s`` adds a sleep per prefill/decode so DAG-overlap behaviour
    is observable in tests and scheduler-overhead benchmarks.
    """

    def __init__(self, batch_slots: int = 4, max_seq: int = 256,
                 vocab: int = 997, bytes_per_token: int = 64,
                 delay_s: float = 0.0):
        self.batch_slots, self.max_seq = batch_slots, max_seq
        self.vocab = vocab
        self.bytes_per_token = bytes_per_token
        self.delay_s = delay_s
        self.prefill_calls = 0
        self.decode_calls = 0
        self._lock = threading.Lock()

    # -- the deterministic token stream ----------------------------------------
    @staticmethod
    def _hash_prompt(prompt: np.ndarray) -> int:
        p = np.asarray(prompt, np.int64)
        return int(np.sum((p + 1) * (np.arange(p.size, dtype=np.int64) + 13))
                   % (1 << 31))

    @classmethod
    def token_at(cls, prompt_hash: int, prompt_len: int, j: int,
                 vocab: int = 997) -> int:
        return int((prompt_hash * 2654435761 + (prompt_len + j) * 40503
                    + j * 97 + 1) % vocab)

    @classmethod
    def expected_tokens(cls, prompt: np.ndarray, max_new: int,
                        eos_token: Optional[int] = None,
                        vocab: int = 997):
        """The oracle: the exact stream a request must produce no matter
        how the scheduler interleaved it."""
        h, plen = cls._hash_prompt(prompt), int(len(prompt))
        out = []
        for j in range(max_new):
            t = cls.token_at(h, plen, j, vocab)
            out.append(t)
            if eos_token is not None and t == eos_token:
                break
        return out

    # -- interface -------------------------------------------------------------
    def init_state(self):
        B = self.batch_slots
        return {"h": np.zeros(B, np.int64), "plen": np.zeros(B, np.int64),
                "emitted": np.zeros(B, np.int64)}

    def _sleep(self):
        if self.delay_s:
            import time
            time.sleep(self.delay_s)

    def prefill(self, prompt: np.ndarray, slot: int):
        with self._lock:
            self.prefill_calls += 1
        self._sleep()
        h, plen = self._hash_prompt(prompt), int(len(prompt))
        return (h, plen), self.token_at(h, plen, 0, self.vocab)

    def insert(self, state, fragment, slot: int):
        h, plen = fragment
        state["h"][slot] = h
        state["plen"][slot] = plen
        state["emitted"][slot] = 1       # prefill emitted token 0
        return state

    def decode(self, state, tokens: np.ndarray, occupied: np.ndarray):
        with self._lock:
            self.decode_calls += 1
        self._sleep()
        out = np.zeros(self.batch_slots, np.int64)
        for i in range(self.batch_slots):
            if not occupied[i]:
                continue
            out[i] = self.token_at(int(state["h"][i]), int(state["plen"][i]),
                                   int(state["emitted"][i]), self.vocab)
            state["emitted"][i] += 1
        return state, out

    def cache_bytes(self, batch: int, seq: int) -> int:
        return batch * seq * self.bytes_per_token

    def compile_stats(self) -> Dict[str, int]:
        with self._lock:
            return {"prefill_calls": self.prefill_calls,
                    "decode_steps": self.decode_calls,
                    "prefill_compiles": 0, "decode_compiles": 0}


__all__ = ["BatchExecutor", "JaxExecutor", "StubExecutor",
           "step_functions"]

"""Device time of one prefill: the ``jit_prefill_fn`` program of
``serving/executor.py``, summed over the traced window, per call."""

LAYER = "model executor"
UNIT = "ms"
MOVES = "ttft_p95_ms"


def read(obs):
    p = obs["trace"].program("jit_prefill_fn") if obs.get("trace") else None
    return None if p is None else 1e3 * p.seconds / p.count

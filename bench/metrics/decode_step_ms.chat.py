"""Device time of one decode step: the ``jit_decode_fn`` program of
``serving/executor.py``, summed over the traced window, per call."""

LAYER = "model executor"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    p = obs["trace"].program("jit_decode_fn") if obs.get("trace") else None
    return None if p is None else 1e3 * p.seconds / p.count

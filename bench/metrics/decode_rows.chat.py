"""Occupied rows per decode step: tokens decoded in the traced window
(seen by the host) over the executor's ``decode_steps`` counter's
increase over it."""

LAYER = "serving scheduler"
UNIT = "rows"
MOVES = "itl_p95_ms"


def read(obs):
    c = obs.get("counters")
    if not c or not c["decode_steps"]:
        return None
    return len(obs["decode_positions"]) / c["decode_steps"]

"""Share of the traced window in which no operation ran on the chip."""

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(obs):
    s = obs.get("trace")
    return None if s is None or not s.devices else 100.0 * s.idle_share()

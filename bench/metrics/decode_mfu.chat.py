"""The decode steps' share of the chip's peak: FLOPs the decoded tokens
of the traced window need (bench/counts/dense_lm.py: attention over the
live context only) over the device time of ``jit_decode_fn`` times the
bf16 peak (bench/peaks.py)."""

LAYER = "model step"
UNIT = "%"
MOVES = "itl_p95_ms"


def read(obs):
    from bench.counts import dense_lm
    from bench.peaks import peaks
    p = obs["trace"].program("jit_decode_fn") if obs.get("trace") else None
    if p is None or not obs["decode_positions"]:
        return None
    flops = sum(dense_lm.decode_token_flops(obs["cfg"], pos)
                for pos in obs["decode_positions"])
    peak = peaks(obs["device_kind"])["flops_bf16"]
    return 100.0 * flops / (p.seconds * peak)

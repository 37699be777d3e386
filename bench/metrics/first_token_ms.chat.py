"""Admission to first token: the mean ``engine.first_token`` span of the
requests admitted in the traced stretch, from the request's admission
into a slot to its token 0 (its prefill, the round's decode, the
insert)."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "ttft_p95_ms"


def read(obs):
    from bench import program_spans as ps
    spans = ps.named(ps.recorded(), "engine.first_token")
    return ps.mean_ms([s.end_ns - s.start_ns for s in spans])

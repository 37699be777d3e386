"""The host turnaround between decode steps: the mean time from the end
of one decode's ``executor.readback`` (its tokens on the host) to the
start of the next decode's ``executor.dispatch``, over consecutive
decodes of the traced stretch."""

LAYER = "serving scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    from bench import program_spans as ps
    record = ps.recorded()
    kids = ps.children(record)
    steps = [(ps.child(kids, d, "executor.dispatch"),
              ps.child(kids, d, "executor.readback"))
             for d in ps.named(record, "executor.decode")]
    steps = [(a, b) for a, b in steps if a is not None and b is not None]
    return ps.mean_ms([nxt[0].start_ns - cur[1].end_ns
                       for cur, nxt in zip(steps, steps[1:])])

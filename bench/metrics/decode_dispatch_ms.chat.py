"""Host time to launch a decode step: the mean ``executor.dispatch``
span under ``executor.decode`` (the copies of tokens and occupancy to
the device and the jitted call returning), over the traced stretch."""

LAYER = "model executor"
UNIT = "ms"
MOVES = "itl_p95_ms"


def read(obs):
    from bench import program_spans as ps
    record = ps.recorded()
    kids = ps.children(record)
    found = [ps.child(kids, d, "executor.dispatch")
             for d in ps.named(record, "executor.decode")]
    return ps.mean_ms([s.end_ns - s.start_ns for s in found
                       if s is not None])

"""Share of prefill positions that are padding: 1 - (prompt tokens /
bucket positions), summed over the ``executor.prefill`` spans of the
traced stretch (each prompt pads to a power-of-two bucket)."""

LAYER = "model executor"
UNIT = "%"
MOVES = "ttft_p95_ms"


def read(obs):
    from bench import program_spans as ps
    spans = ps.named(ps.recorded(), "executor.prefill")
    padded = sum(s.args["bucket"] for s in spans)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(s.args["tokens"] for s in spans) / padded)

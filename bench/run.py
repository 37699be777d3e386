"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its files are
found by name (``bench/workloads/<cell>.json``, its configuration file,
``bench/drivers/<driver>.py``, ``bench/metrics/<metric>.py``).  The run
loads, warms up the cell's own shapes, measures for ``--seconds``, checks
what the timed path produced against the plain reference, and prints one
JSON object as the last line of standard output: the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics (from a profiler trace
of a stretch of the window) with ``--trace 1``.  The numbers compared for
``correct`` are the last lines of standard error and the last key of the
result.  A run that finds no accelerator, or fewer chips than the cell
asks for, exits non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


class RunContext:
    """What a driver is given: the cell, the arguments, and the harness
    helpers (the driver imports nothing of the harness itself)."""

    def __init__(self, cell, args, harness, devices):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.harness = harness
        self.devices = devices
        self.process_t0 = PROCESS_T0
        self.control = False        # bench/control.py: the control is judged


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, allow_cpu: bool = False, root: Path = ROOT) -> int:
    args = parse(argv)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload, root)
        driver = harness.load_driver(cell)
        devices = harness.require_chips(int(cell.entry["chips"]), allow_cpu)
        harness.enable_compile_cache()
        outcome = driver.run(RunContext(cell, args, harness, devices))
    except (harness.SpecError, harness.NoChip) as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 2

    if args.trace:
        metrics, breakdown = {}, None
        if outcome.trace is not None:
            breakdown = outcome.trace.breakdown()
            for m in cell.per_layer:
                try:
                    reader = harness.load_metric(root, m["name"])
                    value = reader.read(outcome.obs)
                except Exception:  # a reader's fault must not hide the rest
                    traceback.print_exc()
                    outcome.errors.append(f"metric {m['name']} failed")
                    continue
                if value is not None:
                    metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    else:
        breakdown = None
        metrics = {}
        for m in cell.end_to_end:
            name = m["name"]
            value = outcome.setup_s if name == "setup_s" \
                else outcome.end_to_end.get(name)
            if value is None:
                outcome.errors.append(f"driver reported no {name}")
                continue
            metrics[name] = {"value": float(value), "unit": m["unit"]}
    harness.print_checks(outcome)
    print(harness.result_line(outcome, metrics, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

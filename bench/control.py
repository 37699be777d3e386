"""The readings that the ``correct`` limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds 25 --seeds 1 2 3

For each seed, in one process, the cell runs as ``bench/run.py`` runs it
(set-up, a window at the cell's own load, the check), with the control
judged in the program's place: the reference computed with float8 (e4m3)
operands, the precision below the configuration's bfloat16, puts a token
first at each position of the sampled requests, and that token's gap
below the float32 reference's best is held to the cell's limit.  So a
control run reads ``correct: false`` where the limit catches the control.
One line per seed gives ``correct``, the control's reading of each number
compared, and the program's reading of the same number from the same
requests.

The limit of each number lies above every program reading and below
every control reading (PERF.md gives both).  The benchmark's own runs do
not run the control.
"""

from __future__ import annotations

import argparse
import json
from math import inf
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench import harness, run

    cell = harness.load_cell(args.workload, ROOT)
    driver = harness.load_driver(cell)
    devices = harness.require_chips(int(cell.entry["chips"]))
    harness.enable_compile_cache()
    rows = []
    for seed in args.seeds:
        ns = run.parse(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds), "--trace", "0"])
        ctx = run.RunContext(cell, ns, harness, devices)
        ctx.control = True
        out = driver.run(ctx)
        row = {"seed": seed, "correct": out.correct,
               "control": {c.name: c.value for c in out.checks},
               "program": {"max_logit_gap": max(out.obs["gaps"] or [inf])},
               "control_per_request": out.obs["control_gaps"],
               "program_per_request": out.obs["gaps"]}
        rows.append(row)
        print("control: " + json.dumps(row), flush=True)
    for side in ("program", "control"):
        print(f"control: {side} readings, largest and smallest " + json.dumps(
            {k: [max(r[side][k] for r in rows), min(r[side][k] for r in rows)]
             for k in rows[0][side]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Find a serving cell's knee: the highest Poisson rate the engine sustains
without a backlog that grows through the window.

    python3 bench/knee_sweep.py --workload <cell> --rates 3 4 5 6 \\
        --seconds 20 --seed 5 [--write] [--table <file.md>]

Each rate runs in a process of its own, one after another, as
``bench/run.py`` runs the cell (set-up, window, check), with the cell's
``rate_rps`` replaced in memory; this process starts them and never touches
the chip.  No file of the benchmark is changed unless ``--write`` is given.
A rate is sustained when the admission queue at the window's end is no
longer than at its start plus :data:`SLACK` requests, and the median TTFT
of the window's last third is at most twice that of its first third plus
50 ms.  The sweep stops after two rates in a row that are not sustained;
the knee is the highest sustained rate below the first rate that is not.
``--write`` stores it in the workload file as ``knee_rps``, with
``rate_rps`` = ``rate_of_knee`` x knee; ``--table`` writes the sweep as a
Markdown table.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path
from typing import List, Optional

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: requests the admission queue may grow by over a sustained window
SLACK = 4
#: prefix of the line in which a rate's process reports its window
LINE = "knee_sweep: "


def sustained(s: dict) -> bool:
    return (s["queue_end"] <= s["queue_start"] + SLACK
            and s["ttft_last_third_ms"] <= 2 * s["ttft_first_third_ms"] + 50)


def knee_of(rows: List[dict]) -> Optional[float]:
    """The highest sustained rate below the first that is not."""
    knee = None
    for s in sorted(rows, key=lambda s: s["rate_rps"]):
        if not s["sustained"]:
            break
        knee = s["rate_rps"]
    return knee


def one_rate(args) -> int:
    """Run the cell at ``args.one_rate`` in this process and report its
    window on one line."""
    from bench import harness, run

    cell = harness.load_cell(args.workload, ROOT)
    driver = harness.load_driver(cell)
    devices = harness.require_chips(int(cell.entry["chips"]))
    harness.enable_compile_cache()
    cell.workload["mix"]["rate_rps"] = args.one_rate
    ns = run.parse(["--workload", args.workload, "--seed", str(args.seed),
                    "--seconds", str(args.seconds), "--trace", "0"])
    obs = driver.run(run.RunContext(cell, ns, harness, devices)).obs
    pool = int(cell.config["serving"]["slots"]) * \
        int(cell.config["serving"]["max_seq"])
    s = dict(obs["sweep"], live_kv_pct=100 * obs["live_positions"] / pool)
    print(LINE + json.dumps(s), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--write", action="store_true")
    ap.add_argument("--table", default=None)
    ap.add_argument("--one-rate", type=float, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.one_rate is not None:
        return one_rate(args)

    rows: List[dict] = []
    for rate in sorted(args.rates):
        child = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()),
             "--workload", args.workload, "--rates", str(rate),
             "--seconds", str(args.seconds), "--seed", str(args.seed),
             "--one-rate", str(rate)],
            stdout=subprocess.PIPE, text=True, check=False)
        sys.stdout.write(child.stdout)
        found = [ln for ln in child.stdout.splitlines()
                 if ln.startswith(LINE)]
        if child.returncode != 0 or not found:
            print(f"knee_sweep: the run at {rate:g} req/s failed "
                  f"(exit code {child.returncode})", file=sys.stderr)
            return 1
        s = json.loads(found[-1][len(LINE):])
        s["sustained"] = sustained(s)
        rows.append(s)
        if len(rows) >= 2 and not (rows[-1]["sustained"]
                                   or rows[-2]["sustained"]):
            break
    knee = knee_of(rows)
    cols = ["rate_rps", "sustained", "queue_start", "queue_end",
            "no_first_token", "ttft_first_third_ms", "ttft_last_third_ms",
            "ttft_p95_ms", "itl_p95_ms", "served_tokens_per_s",
            "live_kv_pct"]
    table = ["| " + " | ".join(cols) + " |",
             "|" + " --- |" * len(cols)]
    for s in rows:
        table.append("| " + " | ".join(
            f"{s[c]:.6g}" if isinstance(s.get(c), float) else str(s.get(c))
            for c in cols) + " |")
    text = f"knee of {args.workload}: {knee} req/s " \
           f"({args.seconds:g} s windows, seed {args.seed})\n\n" + \
        "\n".join(table) + "\n"
    print(text)
    if args.table:
        Path(args.table).parent.mkdir(parents=True, exist_ok=True)
        Path(args.table).write_text(text)
    if args.write and knee is not None:
        path = ROOT / "bench/workloads" / f"{args.workload}.json"
        doc = json.loads(path.read_text())
        doc["mix"]["knee_rps"] = knee
        doc["mix"]["rate_rps"] = round(knee * doc["mix"]["rate_of_knee"], 3)
        path.write_text(json.dumps(doc, indent=1) + "\n")
    return 0 if knee is not None else 1


if __name__ == "__main__":
    sys.exit(main())

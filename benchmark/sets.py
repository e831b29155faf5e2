#!/usr/bin/env python3
"""Run a list of benchmark runs one after the other (one chip call) and
keep each result line: how the bounds and the limits were measured.

    python benchmark/sets.py --tag t1 CELL:SEED[,SEED...]:SECONDS:TRACE[:keep] ...

Each run is `python benchmark/run.py ...` as the driver would start it,
plus `--tag <tag>`, so that its record in chiprun_out/ is not the one a
second set on the same seeds overwrites. Writes chiprun_out/sets-<tag>.jsonl, one line per run: the arguments,
the exit code, wall seconds and the run's last stdout line parsed.
Ends with the quartile spread of every metric per (cell, trace)."""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values: list[float]) -> float | None:
    if len(values) < 3:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv: list[str]) -> int:
    tag = "sets"
    if argv and argv[0] == "--tag":
        tag, argv = argv[1], argv[2:]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"sets-{tag}.jsonl")
    rows = []
    for item in argv:
        cell, seeds, seconds, trace, *keep = item.split(":")
        for seed in seeds.split(","):
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, os.path.join(HERE, "run.py"),
                 "--workload", cell, "--seed", seed, "--seconds", seconds,
                 "--trace", trace, "--tag", tag]
                + (["--keep-trace"] if keep else []),
                cwd=ROOT, capture_output=True, text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except ValueError:
                result = None
            row = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
                   "trace": int(trace), "rc": p.returncode, "wall_s": wall,
                   "result": result}
            rows.append(row)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            brief = {k: v["value"] for k, v in
                     (result or {}).get("metrics", {}).items()}
            print(f"{cell} seed {seed} trace {trace}: rc {p.returncode} "
                  f"wall {wall:.1f} s correct "
                  f"{(result or {}).get('correct')} failed "
                  f"{(result or {}).get('failed')} {json.dumps(brief)}",
                  flush=True)
            if p.returncode or not (result or {}).get("correct"):
                print("---- stdout tail\n" + "\n".join(lines[-25:]))
                print("---- stderr tail\n" + p.stderr[-3000:], flush=True)
    groups: dict = {}
    for r in rows:
        if r["result"]:
            for k, v in r["result"]["metrics"].items():
                groups.setdefault((r["cell"], r["trace"], k), []).append(
                    v["value"])
    for (cell, trace, k), vals in sorted(groups.items()):
        sp = spread(vals)
        print(f"{cell} trace {trace} {k}: n {len(vals)} median "
              f"{statistics.median(vals):.6g} min {min(vals):.6g} max "
              f"{max(vals):.6g} iqr/median "
              f"{'n/a' if sp is None else format(sp, '.4f')}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python3
"""Several runs of one cell in one call, and their spread — how the
bounds in BENCHMARK.json were measured, and how a later PR measures the
parent beside its change (put both checkouts in one call).

    python3 benchmarks/measure.py --workload <cell> --seeds 11,12,13 \
        [--seconds S] [--trace 0|1] [--sets 2]

Each run is ``benchmarks/run.py`` in a process of its own; the result
lines (the numbers compared are their ``checks``) and the ``facts:``
lines go to
``chiprun_out/benchmarks/measure/<cell>.jsonl``. The spread printed per
metric is the builder's: (Q3 - Q1) / median of a set, by
``statistics.quantiles(n=4)``; with two sets of the same seeds, the
wider of the two and the second median over the first.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from rates import iqr_spread  # noqa: E402


def one_run(cell: str, seed: int, seconds: float, trace: int) -> dict:
    argv = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
            cell, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    proc = subprocess.run(argv, cwd=ROOT, capture_output=True, text=True)
    rec = {"seed": seed, "rc": proc.returncode}
    lines = proc.stdout.strip().splitlines()
    for line in lines:
        if line.startswith("facts: "):
            rec["facts"] = json.loads(line[len("facts: "):])
    if proc.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
    else:
        rec["stderr"] = proc.stderr[-3000:]
    return rec


def spread(values) -> float:
    return iqr_spread(values) if len(values) >= 2 else float("nan")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmarks", "measure")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, args.workload + ".jsonl")
    sets = []
    for k in range(args.sets):
        runs = []
        for seed in seeds:
            rec = one_run(args.workload, seed, seconds, args.trace)
            rec["set"] = k
            runs.append(rec)
            with open(out_path, "a") as f:
                f.write(json.dumps(rec) + "\n")
            res = rec.get("result") or {}
            print(json.dumps({
                "set": k, "seed": seed, "rc": rec["rc"],
                "correct": res.get("correct"),
                "metrics": {n: m["value"] for n, m in
                            (res.get("metrics") or {}).items()},
                "checks": {n: c["value"] for n, c in
                           (res.get("checks") or {}).items()},
                "agreement": {
                    k: v for k, v in ((rec.get("facts") or {}).get(
                        "agreement") or {}).items()
                    if "gap" in k or k.endswith("_answers")},
                "stderr": rec.get("stderr", "")[-600:]}), flush=True)
        sets.append(runs)
    names = sorted({n for runs in sets for r in runs
                    for n in (r.get("result") or {}).get("metrics", {})})
    summary = {}
    for n in names:
        per_set = [[r["result"]["metrics"][n]["value"] for r in runs
                    if r.get("result") and n in r["result"]["metrics"]]
                   for runs in sets]
        meds = [statistics.median(v) for v in per_set if v]
        summary[n] = {
            "medians": meds,
            "spreads": [spread(v) for v in per_set],
            "second_over_first": (meds[1] / meds[0] - 1
                                  if len(meds) > 1 else None)}
    print("summary: " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

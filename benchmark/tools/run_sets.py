#!/usr/bin/env python3
"""Run one cell several times, each run a process of its own, and print
each end-to-end metric's median and spread as the contract reads them:
the distance between the first and third quartile
(statistics.quantiles(values, n=4)) as a share of the median.

    python3 benchmark/tools/run_sets.py --workload lsdb100k.flap \\
        --seeds 11,12,13,14,15,16 --seconds 45 --tag set1 [--trace-last]

Every line each run printed goes to chiprun_out/<tag>.jsonl. This process
never touches jax: a chip belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--tag", required=True)
    p.add_argument("--trace-last", action="store_true",
                   help="one more run, on the last seed, with --trace 1")
    p.add_argument("--stop-on-fault", action="store_true",
                   help="stop after a run that is not correct or in whose "
                   "window a program compiled: no chip time for the rest")
    p.add_argument("--extra", default="",
                   help="further arguments for run.py, space-separated")
    args = p.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    runs = [(seed, 0) for seed in seeds]
    if args.trace_last:
        runs.append((seeds[-1], 1))
    results = []
    with open(os.path.join(out_dir, f"{args.tag}.jsonl"), "a") as log:
        for seed, trace in runs:
            cmd = [
                sys.executable, os.path.join(REPO, "benchmark", "run.py"),
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(args.seconds), "--trace", str(trace),
                *args.extra.split(),
            ]
            t0 = time.monotonic()
            proc = subprocess.run(
                cmd, cwd=REPO, capture_output=True, text=True
            )
            wall = time.monotonic() - t0
            lines = [l for l in proc.stdout.splitlines() if l.strip()]
            for line in lines:
                log.write(json.dumps(
                    {"seed": seed, "trace": trace, "line": json.loads(line)}
                ) + "\n")
            last = json.loads(lines[-1]) if lines else {}
            if proc.returncode or "correct" not in last:
                print(json.dumps({
                    "seed": seed, "trace": trace, "rc": proc.returncode,
                    "stderr": proc.stderr[-2000:], "last": last,
                }))
                continue
            print(json.dumps({
                "seed": seed, "trace": trace, "wall_s": round(wall, 1),
                "correct": last["correct"], "failed": last["failed"],
                "attempted": last["attempted"],
                "metrics": {
                    k: v["value"] for k, v in last["metrics"].items()
                },
                "earlier": [json.loads(l) for l in lines[-3:-1]],
            }), flush=True)
            if not trace:
                results.append(last)
            compiled = any(
                json.loads(l).get("compiles_in_window") for l in lines[:-1]
            )
            if args.stop_on_fault and (compiled or not last["correct"]):
                break
    names = sorted({k for r in results for k in r["metrics"]})
    for name in names:
        values = [
            r["metrics"][name]["value"] for r in results
            if name in r["metrics"]
        ]
        row = {"metric": name, "n": len(values), "values": values}
        if len(values) >= 2:
            q1, _, q3 = statistics.quantiles(values, n=4)
            med = statistics.median(values)
            row.update(median=med, spread=(q3 - q1) / med)
        print(json.dumps(row), flush=True)
    ok = len(results) == len(seeds) and all(r["correct"] for r in results)
    print(json.dumps({"tag": args.tag, "all_correct": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

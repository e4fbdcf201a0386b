#!/usr/bin/env python3
"""The builder's tool for a session on the chip: several runs of the
benchmark one after the other in one call, each a process of its own
(as the driver makes them), every last line kept.

    python3 chipbench/measure.py --tag cell1 \\
        --runs mistral7b-decode-closed:6:0 mistral7b-decode-closed:1:1

A run is ``workload:count:trace``; seeds count up from ``--seed0``, the
same in every set, as the builder's contract asks. Lines go to
``chiprun_out/chipbench/<tag>.jsonl`` (one per run, with the workload,
the seed, the exit code and the seconds the process took); the engine's
log of a failed run is kept beside them. It prints a summary last: per
workload and metric the values, the median and the spread (quartile
distance over the median).
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from chipbench.run import run_dir  # noqa: E402
from chipbench.stats import median, spread  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2147483700)
    ap.add_argument("--extra", nargs="*", default=[])
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "chipbench")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, args.tag + ".jsonl")
    rows = []
    for spec in args.runs:
        workload, count, trace = spec.split(":")
        for k in range(int(count)):
            seed = args.seed0 + k
            t0 = time.monotonic()
            proc = subprocess.run(
                [sys.executable, "-m", "chipbench", "--workload", workload,
                 "--seed", str(seed), "--seconds", str(seconds),
                 "--trace", trace, *args.extra],
                cwd=ROOT, capture_output=True, text=True)
            took = time.monotonic() - t0
            last = (proc.stdout.strip().splitlines() or [""])[-1]
            try:
                line = json.loads(last)
            except ValueError:
                line = {"unparsed": last[-500:]}
            row = {"workload": workload, "seed": seed, "trace": int(trace),
                   "rc": proc.returncode, "took_s": took, "line": line,
                   "stderr": proc.stderr[-1500:]}
            rows.append(row)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            if proc.returncode or not line.get("correct"):
                log = os.path.join(run_dir(workload, seed, trace), "logs",
                                   "engine.log")
                if os.path.exists(log):
                    shutil.copy(log, os.path.join(
                        out_dir, f"{args.tag}-{workload}-{seed}.engine.log"))
            print(json.dumps({k: row[k] for k in
                              ("workload", "seed", "trace", "rc", "took_s")}
                             | {"correct": line.get("correct"),
                                "why": line.get("why"),
                                "metrics": {n: m["value"] for n, m in
                                            line.get("metrics", {}).items()}
                                }), flush=True)
    summary = {}
    for row in rows:
        for name, m in row["line"].get("metrics", {}).items():
            summary.setdefault((row["workload"], row["trace"], name),
                               []).append(m["value"])
    for (workload, trace, name), values in sorted(summary.items()):
        print(json.dumps({"workload": workload, "trace": trace,
                          "metric": name, "n": len(values),
                          "median": median(values),
                          "spread": spread(values),
                          "values": values}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

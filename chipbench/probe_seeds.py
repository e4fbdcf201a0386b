#!/usr/bin/env python3
"""The two readings a probe's limits are set between, on the chip, at
the cell's own size, over many seeds in one call:

    python3 chipbench/probe_seeds.py --workload qwen3next-longctx-closed \\
        --seeds 1486797985 3735928559 2718281828 --tag n46 \\
        --control 'fp8:round_to="float8_e4m3fn"' \\
        --report 'bf16:round_to="bfloat16"'

For each seed one engine child (the benchmark's own, ``engine_child.py``
with the cell's configuration and weights from the seed; nothing warmed,
no router, no traffic) serves the probe's three prompts through the
chat endpoint exactly as a run's probe does (``run.run_probe``: the same
prompts, the same executables, the same comparison), so a run's probe
reading on a seed is this tool's on that seed. Of each of the probe's
two numbers (the widest gap of one prompt's twenty, ``gap``; the mean
gap over the run's sixty, ``mean``):

- the LOWER reading: the largest the program reads over the seeds;
- the UPPER reading: the smallest a ``--control NAME:KEY=JSON`` reads:
  the plain reference run with that key laid over the configuration (a
  lower precision: ``round_to`` rounds the residual stream and every
  block's input to that dtype) and PUT IN THE PROGRAM'S PLACE, its own
  top-20 held against the plain reference's;
- ``--report`` reads alike and decides nothing (the reference at the
  SERVED precision: what that precision alone makes of these weights
  and prompts, a second witness beside the program on a seed that
  reads far off).

A line a seed goes to ``chiprun_out/chipbench/<tag>.jsonl`` with what
was served and what each side said, so that another statistic can be
read off the same runs; the summary comes last. Exit 0 if every seed
was read; the limits themselves are set by hand, in the configuration's
file (``harness.probe``), and PERF.md gives the readings. On the chip
only (``--rehearse``: the CPU, tests).
"""

import argparse
import asyncio
import json
import os
import shutil
import sys
import time
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import aiohttp  # noqa: E402

from chipbench import manifest as mf  # noqa: E402
from chipbench import run as bench  # noqa: E402

NOTHING_WARMED = {"shapes": {"decode": [], "prefill": []}}


def keyed(specs: List[str]) -> Dict[str, Dict]:
    """``NAME:KEY=JSON`` (repeatable; one name may come twice and
    gathers its keys) -> name -> {key: value}."""
    out: Dict[str, Dict] = {}
    for spec in specs:
        name, _, rest = spec.partition(":")
        key, _, value = rest.partition("=")
        if not (name and key and value):
            raise SystemExit(f"probe_seeds: {spec!r} is not NAME:KEY=JSON")
        out.setdefault(name, {})[key] = json.loads(value)
    return out


async def one_seed(cell: mf.Cell, seed: int, controls: Dict[str, Dict],
                   rehearse: bool, where: str) -> Dict:
    engine = bench.start_engine(cell, seed, NOTHING_WARMED, where, rehearse)
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10,
                                    sock_read=900)
    try:
        async with aiohttp.ClientSession(timeout=timeout) as session:
            device = await bench.wait_engine(session, engine, cell.chips,
                                             rehearse)
            probe = await bench.run_probe(session, engine.url, cell, seed,
                                          controls)
    finally:
        engine.stop()
    return {"device": device, "probe": probe}


def readings(compared: Dict) -> Dict:
    """A comparison's two numbers: the widest gap of its prompts, the
    mean gap of the run."""
    return {"gap": max(r["max_abs_logprob_diff"]
                       for r in compared["rows"]),
            "mean": compared["mean_abs_logprob_diff"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--report", action="append", default=[],
                    metavar="NAME:KEY=JSON")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--out-dir",
                    default=os.path.join(ROOT, "chiprun_out", "chipbench"))
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--data", action="append", default=[])
    ap.add_argument("--budget-s", type=float, default=None,
                    help="start no seed that, by the last one's time, "
                         "would end after this many seconds (a call cut "
                         "at its limit brings nothing back)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = mf.Cell(mf.load(args.manifest), args.workload, args.data)
    controls, reports = keyed(args.control), keyed(args.report)
    os.makedirs(args.out_dir, exist_ok=True)
    out_path = os.path.join(args.out_dir, args.tag + ".jsonl")
    read: Dict[str, List[Dict]] = {
        n: [] for n in ("program", *controls, *reports)}
    limits, started, took = None, time.monotonic(), 0.0
    for given in args.seeds:
        if args.budget_s and (time.monotonic() - started + 1.2 * took
                              > args.budget_s):
            print(f"probe_seeds: out of time before seed {given}",
                  file=sys.stderr)
            break
        seed = given % 0x7FFFFFFF       # as run.main has it
        where = bench.run_dir(args.workload, given, "probe")
        t0 = time.monotonic()
        try:
            got = asyncio.run(one_seed(cell, seed, {**controls, **reports},
                                       args.rehearse, where))
        except bench.RunFailure as e:
            print(f"probe_seeds: seed {given}: {e}", file=sys.stderr)
            if "runs on" in str(e) or "no accelerator" in str(e):
                return 3
            continue
        shutil.rmtree(where, ignore_errors=True)
        probe, took = got["probe"], time.monotonic() - t0
        with open(out_path, "a") as f:
            f.write(json.dumps({"workload": args.workload, "seed": given,
                                "took_s": took, **got}) + "\n")
        limits = {"gap": probe["tolerance"], "mean": probe["mean_limit"]}
        line = {"seed": given, "took_s": round(took, 1),
                "served_s": round(probe["seconds"], 1)}
        for name, compared in (("program", probe),
                               *probe["detail"]["controls"].items()):
            read[name].append(readings(compared))
            line[name] = {**readings(compared),
                          "gaps": [r["max_abs_logprob_diff"]
                                   for r in compared["rows"]],
                          "shared_top": [r["shared_top"]
                                         for r in compared["rows"]]}
        print(json.dumps(line), flush=True)

    def extreme(names, pick):
        return {n: {k: pick((r[k] for r in read[n]), default=None)
                    for k in ("gap", "mean")} for n in names}
    print(json.dumps({
        "workload": args.workload, "seeds_read": len(read["program"]),
        "limits_now": limits,
        "lower_program_max": extreme(["program"], max)["program"],
        "upper_control_min": extreme(controls, min),
        "report_max": extreme(reports, max)}), flush=True)
    return 0 if len(read["program"]) == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())

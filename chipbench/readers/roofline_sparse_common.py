"""What the sparse-attention roofline readers share: the contexts of
the rows decoding while the profiler was held, the configuration's
file, and the held experts a layer of a step read."""

import json

from perf_delta import read as read_share


def live_contexts(run):
    """The context (prompt plus tokens received) of every request that
    was decoding at the middle of the traced interval."""
    t = run["trace"]
    mid = (t["started_unix"] + t["held_s"] / 2
           - (run["window"]["t0_unix"] - run["window"]["t0"]))
    out = []
    for r in run["records"]:
        times = r["token_times"]
        if times and times[0] <= mid and (
                len(times) < r["max_tokens"] or times[-1] > mid):
            out.append(r["prompt_tokens"] + sum(1 for x in times
                                                if x <= mid))
    return out


def config(run):
    with open(run["config_file"]) as f:
        return json.load(f)


def experts_touched(run, hf):
    """Held experts a layer of a decode step read: the program's
    counter ``totals.moe`` over the window, of the experts held."""
    share = read_share(run, ["totals.moe.experts_read"],
                       ["totals.moe.experts_resident"])      # in %
    return None if share is None else hf["n_routed_experts"] * share / 100.0

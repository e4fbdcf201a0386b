"""``decode_step_roofline``: 100 x the least time one decode step could
take (chipbench/roofline.py, from the configuration's shapes and the
rows and contexts live while the profiler was held) over the device
time one step took (trace_module, ``per: step``)."""

import json

from trace_module import module_ms

from chipbench import roofline


def live_rows_and_context(run):
    """Requests that were decoding at the middle of the traced
    interval, and the sum of their contexts then (prompt plus tokens
    received)."""
    t = run["trace"]
    mid = (t["started_unix"] + t["held_s"] / 2
           - (run["window"]["t0_unix"] - run["window"]["t0"]))
    rows = ctx = 0
    for r in run["records"]:
        times = r["token_times"]
        if times and times[0] <= mid and (
                len(times) < r["max_tokens"] or times[-1] > mid):
            rows += 1
            ctx += r["prompt_tokens"] + sum(1 for x in times if x <= mid)
    return rows, ctx


def read(run, kernel: str):
    if not run.get("trace"):
        return None
    step_ms = module_ms(run, kernel, per="step")
    rows, ctx = live_rows_and_context(run)
    if not step_ms or not rows:
        return None
    with open(run["config_file"]) as f:
        hf = json.load(f)
    needs = roofline.decode_step_needs(
        hf, rows, ctx,
        weight_bytes_per_param=1.0 if hf.get("quantization") == "int8"
        else 2.0)
    least = roofline.least_seconds(needs, run["device"]["kind"])
    run.setdefault("notes", {})["decode_step_roofline"] = {
        **least, "rows": rows, "context_tokens": ctx}
    return 100.0 * 1e3 * least["seconds"] / step_ms

"""``latent_decode_step_roofline``: roofline_decode's share for a
latent-attention mixture of experts — 100 x the least time one decode
step could take (chipbench/roofline_latent.py: the configuration's
published keys, the rows and contexts live while the profiler was
held, and the routed experts a layer of a step read, which is the
program's counter ``totals.moe`` of GET /debug/perf over the window:
``experts_read`` of ``experts_resident``, as ``moe_read_share`` reads
it; peaks and ``least_seconds`` of chipbench/roofline.py) over the
device time one step took (trace_module, ``per: step``). A program
without that counter gives nothing to read: None."""

import json

from perf_delta import read as read_share
from roofline_decode import live_rows_and_context
from trace_module import module_ms

from chipbench import roofline, roofline_latent


def read(run, kernel: str):
    if not run.get("trace"):
        return None
    step_ms = module_ms(run, kernel, per="step")
    rows, ctx = live_rows_and_context(run)
    share = read_share(run, ["totals.moe.experts_read"],
                       ["totals.moe.experts_resident"])      # in %
    if not step_ms or not rows or share is None:
        return None
    with open(run["config_file"]) as f:
        hf = json.load(f)
    touched = hf["n_routed_experts"] * share / 100.0
    needs = roofline_latent.decode_step_needs(
        hf, rows, ctx, touched,
        weight_bytes_per_param=1.0 if hf.get("quantization") == "int8"
        else 2.0)
    least = roofline.least_seconds(needs, run["device"]["kind"])
    # chipbench/run.py copies ONE key of run["notes"] into the line's
    # notes.roofline, "decode_step_roofline", whichever reader filed
    # it: in a cell of this family that is this yardstick's bound,
    # rows, context and experts, named as such
    run.setdefault("notes", {})["decode_step_roofline"] = {
        **least, "rows": rows, "context_tokens": ctx,
        "experts_touched": touched, "yardstick": "roofline_latent"}
    return 100.0 * 1e3 * least["seconds"] / step_ms

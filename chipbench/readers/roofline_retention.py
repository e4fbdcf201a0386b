"""The power retention rooflines (Brumby's cell; yardstick
chipbench/roofline_retention.py: a live row's state READ once a call,
its write not counted):

``what: "step"``  ``retention_decode_step_roofline``: 100 x the least
    time one decode step could take (every weight once, the state of
    the rows decoding while the profiler was held once a layer) over
    the step's device time: the seconds of the executables that run the
    file's ``harness.decode_step.op`` over its calls a step.
``what: "decode"`` / ``"prefill"``  ``retention_decode_kernel_roofline``
    / ``retention_prefill_kernel_roofline``: 100 x the least time ONE
    call of the kernel could take over the device time a call took
    (``kernel``'s seconds over its calls in the executables that run
    it). A decode call is one position of every row decoding while the
    profiler was held; a prefill call the positions a prefill dispatch
    computed on average over the window (``totals.prefill``: real and
    padded, as the kernel runs them), of its rows.

No trace, no such operation, or a file that is not such a model's:
None."""

from _common import dig
from roofline_hybrid_common import (bytes_per_param, config, live_contexts,
                                    moved)
from trace_module import module_ms, modules_with

from chipbench import harness_key, roofline, roofline_retention


def read(run, what: str, kernel: str = ""):
    if not run.get("trace"):
        return None
    hf = config(run)
    if hf.get("model_type") != "brumby":
        return None
    kind = run["device"]["kind"]
    rows = len(live_contexts(run))
    if what == "step":
        op = harness_key.read(run["config_file"])["decode_step"]["op"]
        ms = module_ms(run, op, "step")
        if not ms or not rows:
            return None
        least = roofline.least_seconds(
            roofline_retention.decode_step_needs(hf, rows,
                                                 bytes_per_param(hf)),
            kind)
        run.setdefault("notes", {})["decode_step_roofline"] = {
            **least, "rows": rows, "yardstick": "roofline_retention"}
        return 100.0 * 1e3 * least["seconds"] / ms
    mods = modules_with(run, kernel)
    calls = sum(m["ops"][kernel][0] for m in mods)
    seconds = sum(m["ops"][kernel][1] for m in mods)
    if not calls or not seconds:
        return None
    if what == "decode":
        tokens = rows
    else:
        dispatches = moved(run, "totals.prefill.dispatches")
        real, pad = (moved(run, "totals.prefill." + k)
                     for k in ("real", "pad"))
        by_rows = dig(run["perf_close"], "totals.prefill.by_rows") or {}
        before = dig(run["perf_open"], "totals.prefill.by_rows") or {}
        if not dispatches or real is None or pad is None:
            return None
        # the rows a dispatch ran, on average over the window
        rows = sum(int(r) * (n - before.get(r, 0))
                   for r, n in by_rows.items()) / dispatches or 1
        tokens = (real + pad) / dispatches
    if not rows or not tokens:
        return None
    least = roofline.least_seconds(
        roofline_retention.retention_call_needs(hf, rows, tokens), kind)
    return 100.0 * least["seconds"] * calls / seconds

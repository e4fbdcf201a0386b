"""Nemotron-H's rooflines (the cell ``nemotron3nano-longctx-closed``;
yardstick chipbench/roofline_nemotronh.py):

``what: "step"``  ``nemotronh_decode_step_roofline``: 100 x the least
    time one decode step could take (every weight outside the routed
    experts once, the experts ``totals.moe`` counted the rows to have
    hit, K and V of the rows decoding while the profiler was held, a
    state page in and out a Mamba-2 block) over the step's device time:
    the seconds of the executables that run the file's
    ``harness.decode_step.op`` over its calls a step.
``what: "chunk"``  ``nemotronh_prefill_chunk_roofline``: 100 x the
    least time a prefill chunk of ``tokens`` tokens could take (the
    larger of its operations at the matrix unit's peak and its bytes;
    at the keys in context a query that ``totals.state`` moved by over
    the window and the assignments ``totals.prefill.held_rows`` counted
    to have landed on the held experts) over the median device time of
    the prefill executable that ran most while traced (the file's
    ``harness.prefill_dispatch.op``).
``what: "decode"`` / ``"prefill"``  ``ssd_decode_kernel_roofline`` /
    ``ssd_prefill_kernel_roofline``: 100 x the least time ONE call of
    the kernel could take over the device time a call took
    (``kernel``'s seconds over its calls in the executables that run
    it). A decode call is one position of every row decoding while the
    profiler was held; a prefill call the positions a prefill dispatch
    computed on average over the window (``totals.prefill``: real and
    padded, as the kernel runs them), of its rows.

No trace, no such operation or counter (a program without them), or a
file that is not such a model's: None."""

from _common import dig
from perf_delta import read as read_share
from roofline_hybrid_common import bytes_per_param, moved
from roofline_sparse_common import config, live_contexts
from trace_module import module_ms, modules_with

from chipbench import harness_key, roofline_nemotronh


def _dispatch_rows(run, dispatches):
    """The rows a prefill dispatch ran, on average over the window
    (``totals.prefill.by_rows``: dispatches by their rows)."""
    now = dig(run["perf_close"], "totals.prefill.by_rows") or {}
    before = dig(run["perf_open"], "totals.prefill.by_rows") or {}
    return sum(int(r) * (n - before.get(r, 0))
               for r, n in now.items()) / dispatches or 1


def read(run, what: str, kernel: str = "", tokens: int = 0):
    hf = config(run)
    if not roofline_nemotronh.is_nemotronh(hf) or not run.get("trace"):
        return None
    kind = run["device"]["kind"]
    harness = harness_key.read(run["config_file"])
    blocks = roofline_nemotronh.sizes(hf)
    if what == "step":
        contexts = live_contexts(run)
        ms = module_ms(run, harness["decode_step"]["op"], "step")
        share = read_share(run, ["totals.moe.experts_read"],
                           ["totals.moe.experts_resident"])
        if not ms or not contexts or share is None:
            return None
        least = roofline_nemotronh.least_seconds(
            roofline_nemotronh.decode_step_needs(
                hf, len(contexts), float(sum(contexts)),
                blocks["held"] * share / 100.0, bytes_per_param(hf)), kind)
        run.setdefault("notes", {})["decode_step_roofline"] = {
            **least, "rows": len(contexts),
            "context_tokens": sum(contexts),
            "experts_read_a_block": blocks["held"] * share / 100.0,
            "yardstick": "roofline_nemotronh"}
        return 100.0 * 1e3 * least["seconds"] / ms
    dispatches = moved(run, "totals.prefill.dispatches")
    if what == "chunk":
        ms = module_ms(run, harness["prefill_dispatch"]["op"], "dispatch")
        queries = moved(run, "totals.state.scan_tokens")
        keys = moved(run, "totals.state.prefill_keys")
        held = moved(run, "totals.prefill.held_rows")
        if not ms or not queries or keys is None or not dispatches:
            return None
        least = roofline_nemotronh.least_seconds(
            roofline_nemotronh.prefill_chunk_needs(
                hf, tokens, keys / queries,
                None if not held else held / (dispatches * blocks["moe"]),
                bytes_per_param(hf)), kind)
        return 100.0 * 1e3 * least["seconds"] / ms
    mods = modules_with(run, kernel)
    calls = sum(m["ops"][kernel][0] for m in mods)
    seconds = sum(m["ops"][kernel][1] for m in mods)
    if not calls or not seconds:
        return None
    if what == "decode":
        rows = positions = len(live_contexts(run))
    else:
        real, pad = (moved(run, "totals.prefill." + k)
                     for k in ("real", "pad"))
        if not dispatches or real is None or pad is None:
            return None
        rows = _dispatch_rows(run, dispatches)
        positions = (real + pad) / dispatches
    if not rows or not positions:
        return None
    least = roofline_nemotronh.least_seconds(
        roofline_nemotronh.ssd_call_needs(hf, rows, positions), kind)
    return 100.0 * least["seconds"] * calls / seconds

"""The decoder-hybrid-decoder's rooflines (Phi-4-mini-flash's cell;
yardstick chipbench/roofline_yoco.py):

``what: "step"``  ``yoco_decode_step_roofline``: 100 x the least time
    one decode step could take (every weight once, the full layer's
    K/V of the rows decoding while the profiler was held once a READER,
    the windows, a Mamba page in and out a Mamba layer) over the step's
    device time: the seconds of the executables that run the file's
    ``harness.decode_step.op`` over its calls a step.
``what: "chunk"``  ``yoco_prefill_chunk_roofline``: 100 x the least
    time a prefill chunk of ``tokens`` tokens could take over the
    layers before the first gated memory unit (at the keys in context a
    query that ``totals.state`` moved by over the window, and one
    position of the later layers in the share of dispatches that ended
    a prompt, ``totals.prefill.cross_positions``) over the median
    device time of the prefill executable that ran most while traced
    (the file's ``harness.prefill_dispatch.op``).
``what: "decode"`` / ``"prefill"``  ``mamba_decode_kernel_roofline`` /
    ``mamba_prefill_kernel_roofline``: 100 x the least time ONE call of
    the kernel could take over the device time a call took
    (``kernel``'s seconds over its calls in the executables that run
    it). A decode call is one position of every row decoding while the
    profiler was held; a prefill call the positions a prefill dispatch
    computed on average over the window (``totals.prefill``: real and
    padded, as the kernel runs them), of its rows.
``what: "shared"``  ``shared_kv_step_share`` (no trace needed): 100 x
    the bytes of the ONE shared K/V layer its readers that append
    nothing read a decode step (``totals.shared_kv.keys_read`` over
    ``totals.state.steps``, times a token's K and V) over the step's
    least bytes at the window's average rows and context.

No trace where one is needed, no such operation or counter, or a file
that is not such a model's: None."""

from _common import dig
from roofline_hybrid_common import (bytes_per_param, config, live_contexts,
                                    moved)
from trace_module import module_ms, modules_with

from chipbench import harness_key, roofline_yoco


def _dispatch_rows(run, dispatches):
    """The rows a prefill dispatch ran, on average over the window."""
    now = dig(run["perf_close"], "totals.prefill.by_rows") or {}
    before = dig(run["perf_open"], "totals.prefill.by_rows") or {}
    return sum(int(r) * (n - before.get(r, 0))
               for r, n in now.items()) / dispatches or 1


def _shared(run, hf):
    steps, rows, keys = (moved(run, "totals." + p) for p in (
        "state.steps", "state.step_rows", "shared_kv.keys_read"))
    cross = roofline_yoco.sizes(hf)["cross"]
    if not steps or not rows or not keys:
        return None
    live, context = rows / steps, keys / (cross * rows)
    needs = roofline_yoco.decode_step_needs(
        hf, live, live * context,
        live * min(context, roofline_yoco.sizes(hf)["W"]),
        bytes_per_param(hf))
    return (100.0 * keys / steps * roofline_yoco.kv_token_bytes(hf)
            / needs["bytes"])


def read(run, what: str, kernel: str = "", tokens: int = 0):
    hf = config(run)
    if not roofline_yoco.is_yoco(hf):
        return None
    if what == "shared":
        return _shared(run, hf)
    if not run.get("trace"):
        return None
    kind = run["device"]["kind"]
    harness = harness_key.read(run["config_file"])
    if what == "step":
        contexts = live_contexts(run)
        ms = module_ms(run, harness["decode_step"]["op"], "step")
        if not ms or not contexts:
            return None
        least = roofline_yoco.least_seconds(
            roofline_yoco.decode_step_needs(
                hf, *roofline_yoco.context_sums(hf, contexts),
                bytes_per_param(hf)), kind)
        run.setdefault("notes", {})["decode_step_roofline"] = {
            **least, "rows": len(contexts),
            "context_tokens": sum(contexts), "yardstick": "roofline_yoco"}
        return 100.0 * 1e3 * least["seconds"] / ms
    dispatches = moved(run, "totals.prefill.dispatches")
    if what == "chunk":
        ms = module_ms(run, harness["prefill_dispatch"]["op"], "dispatch")
        queries = moved(run, "totals.state.scan_tokens")
        keys = moved(run, "totals.state.prefill_keys")
        ended = moved(run, "totals.prefill.cross_positions")
        if not ms or not queries or keys is None or not dispatches:
            return None
        finishing = min(1.0, (ended or 0) / (
            dispatches * _dispatch_rows(run, dispatches)))
        least = roofline_yoco.least_seconds(
            roofline_yoco.prefill_chunk_needs(
                hf, tokens, keys / queries, finishing,
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
    least = roofline_yoco.least_seconds(
        roofline_yoco.mamba_call_needs(hf, rows, positions), kind)
    return 100.0 * least["seconds"] * calls / seconds

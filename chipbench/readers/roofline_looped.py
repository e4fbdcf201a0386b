"""A looped decoder's rooflines (the cell ``ouro26b-decode-closed``;
yardstick chipbench/roofline_looped.py):

``what: "step"``  ``looped_decode_step_roofline``: 100 x the least time
    one decode step could take (every layer's weights once a pass, the
    head, K and V of the rows decoding while the profiler was held in
    every pool layer) over the step's device time: the seconds of the
    executables that run the file's ``harness.decode_step.op`` over its
    calls a step.
``what: "chunk"``  ``looped_prefill_chunk_roofline``: 100 x the least
    time a prefill dispatch of the chunk bucket ``tokens`` could take
    (the larger of its operations at the matrix unit's peak and its
    bytes; the rows a dispatch ran on average over the window,
    ``totals.prefill.by_rows``, each of the mean length of the window's
    prompts that land in that bucket) over the median device time of
    the prefill executable that ran most while traced (the file's
    ``harness.prefill_dispatch.op``).
``what: "attention"``  ``looped_attention_kernel_roofline``: 100 x the
    least time ONE call of the decode attention kernel could take (K
    and V of the live contexts in one pool layer) over the device time
    a call took (``kernel``'s seconds over its calls).
``what: "kv_share"``  ``loop_kv_step_share``: 100 x K/V's part of a
    decode step's least bytes, from the program's own byte model
    (``totals.step_bytes``: the weights a pass times the passes, the
    head, K and V a cached position) at the contexts of the rows
    decoding at the window's middle. No trace needed.

No trace (where one is read), no such operation or counter (a program
without them), or a file that is not such a model's: None."""

import json

from _common import dig
from trace_module import module_ms, modules_with

from chipbench import harness_key, roofline_looped


def _config(run):
    with open(run["config_file"]) as f:
        return json.load(f)


def _contexts_at(run, at: float):
    """The context (prompt plus tokens received) of every request that
    was decoding at ``at`` on the clients' clock."""
    out = []
    for r in run["records"]:
        times = r["token_times"]
        if times and times[0] <= at and (
                len(times) < r["max_tokens"] or times[-1] > at):
            out.append(r["prompt_tokens"] + sum(1 for x in times
                                                if x <= at))
    return out


def _traced_contexts(run):
    t = run["trace"]
    return _contexts_at(run, t["started_unix"] + t["held_s"] / 2
                        - (run["window"]["t0_unix"] - run["window"]["t0"]))


def _weight_bytes(hf) -> float:
    return 1.0 if hf.get("quantization") == "int8" else 2.0


def _dispatch_rows(run):
    """The rows a prefill dispatch ran, on average over the window."""
    now = dig(run["perf_close"], "totals.prefill.by_rows") or {}
    before = dig(run["perf_open"], "totals.prefill.by_rows") or {}
    n = sum(v - before.get(r, 0) for r, v in now.items())
    return sum(int(r) * (v - before.get(r, 0))
               for r, v in now.items()) / n if n else None


def read(run, what: str, kernel: str = "", tokens: int = 0):
    hf = _config(run)
    if not roofline_looped.is_looped(hf):
        return None
    if what == "kv_share":
        parts = dig(run["perf_close"], "totals.step_bytes")
        w = run["window"]
        contexts = _contexts_at(run, (w["t0"] + w["t1"]) / 2)
        if not parts or not contexts:
            return None
        kv = (sum(contexts) + len(contexts)) * parts["kv_per_position"]
        return 100.0 * kv / (kv + parts["weights"] + parts["head"])
    if not run.get("trace"):
        return None
    kind = run["device"]["kind"]
    harness = harness_key.read(run["config_file"])
    if what == "step":
        contexts = _traced_contexts(run)
        ms = module_ms(run, harness["decode_step"]["op"], "step")
        if not ms or not contexts:
            return None
        least = roofline_looped.least_seconds(
            roofline_looped.decode_step_needs(
                hf, len(contexts), float(sum(contexts)), _weight_bytes(hf)),
            kind)
        run.setdefault("notes", {})["decode_step_roofline"] = {
            **least, "rows": len(contexts),
            "context_tokens": sum(contexts),
            "bytes_by_part": roofline_looped.decode_step_parts(
                hf, len(contexts), float(sum(contexts)), _weight_bytes(hf)),
            "yardstick": "roofline_looped"}
        return 100.0 * 1e3 * least["seconds"] / ms
    if what == "chunk":
        ms = module_ms(run, harness["prefill_dispatch"]["op"], "dispatch")
        rows = _dispatch_rows(run)
        lengths = [r["prompt_tokens"] for r in run["records"]
                   if tokens // 2 < r["prompt_tokens"] <= tokens]
        if not ms or not rows or not lengths:
            return None
        least = roofline_looped.least_seconds(
            roofline_looped.prefill_chunk_needs(
                hf, rows, sum(lengths) / len(lengths), 0.0,
                _weight_bytes(hf)), kind)
        return 100.0 * 1e3 * least["seconds"] / ms
    if what == "attention":
        mods = modules_with(run, kernel)
        calls = sum(m["ops"][kernel][0] for m in mods)
        seconds = sum(m["ops"][kernel][1] for m in mods)
        contexts = _traced_contexts(run)
        if not calls or not seconds or not contexts:
            return None
        least = roofline_looped.least_seconds(
            roofline_looped.attention_call_needs(
                hf, len(contexts), float(sum(contexts))), kind)
        return 100.0 * least["seconds"] * calls / seconds
    raise ValueError(f"unknown what {what!r}")

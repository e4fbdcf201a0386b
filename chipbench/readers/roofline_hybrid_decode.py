"""``hybrid_decode_step_device_ms`` (``what: "ms"``): the device
milliseconds one decode step of a hybrid model took, the decode
executables' seconds over ``kernel``'s calls an ATTENTION layer
(roofline_hybrid_common.step_ms). ``hybrid_decode_step_roofline``
(``what: "roofline"``): 100 x the least time that step could take
(chipbench/roofline_hybrid.py: published keys, the contexts live while
the profiler was held, the held experts at what ``totals.moe`` counted)
over it. No trace, no counter or a file that is no hybrid's: None."""

from roofline_hybrid_common import (bytes_per_param, config,
                                    experts_touched, is_hybrid,
                                    live_contexts, step_ms)

from chipbench import roofline, roofline_hybrid


def read(run, kernel: str, what: str):
    if not run.get("trace"):
        return None
    hf = config(run)
    if not is_hybrid(hf):
        return None
    ms = step_ms(run, hf, kernel)
    if what == "ms" or not ms:
        return ms
    contexts, touched = live_contexts(run), experts_touched(run, hf)
    if not contexts or touched is None:
        return None
    least = roofline.least_seconds(
        roofline_hybrid.decode_step_needs(hf, contexts, touched,
                                          bytes_per_param(hf)),
        run["device"]["kind"])
    run.setdefault("notes", {})["decode_step_roofline"] = {
        **least, "rows": len(contexts), "context_tokens": sum(contexts),
        "experts_touched": touched, "yardstick": "roofline_hybrid"}
    return 100.0 * 1e3 * least["seconds"] / ms

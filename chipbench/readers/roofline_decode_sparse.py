"""``sparse_decode_step_roofline``: 100 x the least time one decode
step of a model with a sparse-attention indexer could take
(chipbench/roofline_sparse.py: published keys, the contexts live while
the profiler was held, min(context, index_topk) latents a row, the held
experts at what ``totals.moe`` counted) over the device time one step
took (trace_module, ``per: step``). No trace, no counter or a file
without an indexer: None."""

from roofline_sparse_common import config, experts_touched, live_contexts
from trace_module import module_ms

from chipbench import roofline, roofline_sparse


def read(run, kernel: str):
    if not run.get("trace"):
        return None
    hf = config(run)
    step_ms = module_ms(run, kernel, per="step")
    contexts = live_contexts(run)
    if not step_ms or not contexts or "index_topk" not in hf:
        return None
    touched = experts_touched(run, hf)
    if touched is None:
        return None
    least = roofline.least_seconds(
        roofline_sparse.decode_step_needs(
            hf, contexts, touched,
            weight_bytes_per_param=1.0 if hf.get("quantization") == "int8"
            else 2.0),
        run["device"]["kind"])
    run.setdefault("notes", {})["decode_step_roofline"] = {
        **least, "rows": len(contexts), "context_tokens": sum(contexts),
        "experts_touched": touched, "yardstick": "roofline_sparse"}
    return 100.0 * 1e3 * least["seconds"] / step_ms

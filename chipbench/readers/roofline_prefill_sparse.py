"""``sparse_prefill_chunk_roofline``: 100 x the least time a prefill
chunk of ``tokens`` tokens could take (chipbench/roofline_sparse.py
``prefill_chunk_needs``, at the keys in context, scored and attended a
query that the program's counter ``totals.sparse.prefill`` moved by
over the window: the window's average chunk) over the median device
time of the prefill executable that ran most while traced (the
executables that run ``kernel``). No trace, no counter: None."""

from _common import dig
from roofline_sparse_common import config
from trace_module import module_ms

from chipbench import roofline, roofline_sparse


def read(run, kernel: str, tokens: int):
    if not run.get("trace"):
        return None
    hf = config(run)
    chunk_ms = module_ms(run, kernel, per="dispatch")
    moved = {}
    for key in ("queries", "keys_in_context", "keys_scored",
                "keys_attended"):
        a, b = (dig(run[at], "totals.sparse.prefill." + key)
                for at in ("perf_open", "perf_close"))
        if a is None or b is None:
            return None
        moved[key] = b - a
    if not chunk_ms or not moved["queries"] or "index_topk" not in hf:
        return None
    per = {k: v / moved["queries"] for k, v in moved.items()}
    least = roofline.least_seconds(
        roofline_sparse.prefill_chunk_needs(
            hf, tokens, per["keys_in_context"], per["keys_scored"],
            per["keys_attended"],
            weight_bytes_per_param=1.0 if hf.get("quantization") == "int8"
            else 2.0),
        run["device"]["kind"])
    return 100.0 * 1e3 * least["seconds"] / chunk_ms

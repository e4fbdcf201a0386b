"""``hybrid_prefill_chunk_roofline``: 100 x the least time a prefill
chunk of ``tokens`` tokens of a hybrid model could take
(chipbench/roofline_hybrid.py ``prefill_chunk_needs``, at the keys in
context a query that the program's counter ``totals.state`` moved by
over the window: the window's average chunk) over the median device
time of the prefill executable that ran most while traced (of those
that run ``kernel``). No trace, no counter: None."""

from roofline_hybrid_common import bytes_per_param, config, is_hybrid, moved
from trace_module import module_ms

from chipbench import roofline, roofline_hybrid


def read(run, kernel: str, tokens: int):
    if not run.get("trace"):
        return None
    hf = config(run)
    chunk_ms = module_ms(run, kernel, per="dispatch")
    queries = moved(run, "totals.state.scan_tokens")
    keys = moved(run, "totals.state.prefill_keys")
    if not chunk_ms or not queries or keys is None or not is_hybrid(hf):
        return None
    least = roofline.least_seconds(
        roofline_hybrid.prefill_chunk_needs(hf, tokens, keys / queries,
                                            bytes_per_param(hf)),
        run["device"]["kind"])
    return 100.0 * 1e3 * least["seconds"] / chunk_ms

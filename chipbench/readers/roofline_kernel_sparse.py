"""``indexer_kernel_roofline`` and ``sparse_attention_kernel_roofline``:
100 x the least time ONE decode call of a kernel could take
(chipbench/roofline_sparse.py: ``what`` "index", every key of the live
contexts against the index queries; "attention", min(context,
index_topk) latents a row) over the device time a call took: the
``kernel`` operation's seconds over its calls in the decode
executables (those that run ``within``). No trace or no such
operation: None."""

from roofline_sparse_common import config, live_contexts
from trace_module import modules_with

from chipbench import roofline, roofline_sparse

NEEDS = {"index": roofline_sparse.index_call_needs,
         "attention": roofline_sparse.attention_call_needs}


def read(run, kernel: str, within: str, what: str):
    mods = [m for m in modules_with(run, within) if kernel in m["ops"]]
    calls = sum(m["ops"][kernel][0] for m in mods)
    seconds = sum(m["ops"][kernel][1] for m in mods)
    hf = config(run)
    if not calls or not seconds or "index_topk" not in hf:
        return None
    contexts = live_contexts(run)
    if not contexts:
        return None
    least = roofline.least_seconds(NEEDS[what](hf, contexts),
                                   run["device"]["kind"])
    return 100.0 * least["seconds"] * calls / seconds

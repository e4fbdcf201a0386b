"""What the hybrid roofline readers share: the configuration's file,
the decode executables' device milliseconds a STEP (a step calls the
attention kernel once an ATTENTION layer, which is every
``full_attention_interval``-th: trace_module's ``per: step`` divides by
``num_hidden_layers`` and reads that many times a step here), the held
experts a layer of a step read, and a counter's movement over the
window."""

from _common import dig
from perf_delta import read as read_share
from roofline_sparse_common import config, live_contexts  # noqa: F401
from trace_module import modules_with

from chipbench import roofline_hybrid


def is_hybrid(hf) -> bool:
    return "full_attention_interval" in hf and "linear_num_value_heads" in hf


def step_ms(run, hf, kernel: str):
    """Device milliseconds of one decode step: all runs' seconds of the
    executables that run ``kernel`` over its calls an attention layer."""
    mods = modules_with(run, kernel)
    steps = (sum(m["ops"][kernel][0] for m in mods)
             / roofline_hybrid.layer_counts(hf)[1]) if mods else 0
    return 1e3 * sum(m["total_s"] for m in mods) / steps if steps else None


def experts_touched(run, hf):
    share = read_share(run, ["totals.moe.experts_read"],
                       ["totals.moe.experts_resident"])      # in %
    return None if share is None else hf["num_experts"] * share / 100.0


def moved(run, path: str):
    a, b = (dig(run[at], path) for at in ("perf_open", "perf_close"))
    return None if a is None or b is None else b - a


def bytes_per_param(hf) -> float:
    return 1.0 if hf.get("quantization") == "int8" else 2.0

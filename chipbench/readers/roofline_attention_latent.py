"""``latent_attention_kernel_roofline``: 100 x the least time ONE call
of the latent decode-attention kernel could take (the larger of the
live tokens' cached vectors over the peak bandwidth and the call's
operations over the peak bf16 rate: chipbench/roofline_latent.py
``attention_call_needs``, rows and contexts as live while the profiler
was held) over the device time a call took: the ``kernel`` operation's
seconds over its calls in the decode executables
(``modules[*].ops[kernel]`` = [calls, seconds])."""

import json

from roofline_decode import live_rows_and_context
from trace_module import modules_with

from chipbench import roofline, roofline_latent


def read(run, kernel: str):
    mods = modules_with(run, kernel)
    calls = sum(m["ops"][kernel][0] for m in mods)
    seconds = sum(m["ops"][kernel][1] for m in mods)
    if not calls or not seconds:
        return None
    rows, ctx = live_rows_and_context(run)
    if not rows:
        return None
    with open(run["config_file"]) as f:
        hf = json.load(f)
    least = roofline.least_seconds(
        roofline_latent.attention_call_needs(hf, ctx),
        run["device"]["kind"])
    return 100.0 * least["seconds"] * calls / seconds

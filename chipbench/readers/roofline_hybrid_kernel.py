"""``gdn_decode_kernel_roofline`` and ``gdn_prefill_kernel_roofline``:
100 x the least time ONE call of a Gated DeltaNet kernel could take
(chipbench/roofline_hybrid.py ``gdn_call_needs``: the recurrence's
operations, q, k, v, g, beta in and o out, each live row's matrices in
and out once) over the device time a call took: the ``kernel``
operation's seconds over its calls in the executables that run
``within``. ``what: "decode"``: a call is one position of each row
decoding while the profiler was held. ``what: "prefill"``: a call is
one row's chunk, of the positions a prefill dispatch computed on
average over the window (``totals.prefill``: real and padded, as the
kernel runs them). No trace or no such operation: None."""

from roofline_hybrid_common import config, is_hybrid, live_contexts, moved
from trace_module import modules_with

from chipbench import roofline, roofline_hybrid


def read(run, kernel: str, within: str, what: str):
    mods = [m for m in modules_with(run, within) if kernel in m["ops"]]
    calls = sum(m["ops"][kernel][0] for m in mods)
    seconds = sum(m["ops"][kernel][1] for m in mods)
    hf = config(run)
    if not calls or not seconds or not is_hybrid(hf):
        return None
    if what == "decode":
        rows = len(live_contexts(run))
        tokens = rows
    else:
        rows, dispatches = 1, moved(run, "totals.prefill.dispatches")
        real, pad = (moved(run, "totals.prefill." + k)
                     for k in ("real", "pad"))
        tokens = (real + pad) / dispatches if dispatches else 0
    if not rows or not tokens:
        return None
    least = roofline.least_seconds(
        roofline_hybrid.gdn_call_needs(hf, rows, tokens),
        run["device"]["kind"])
    return 100.0 * least["seconds"] * calls / seconds

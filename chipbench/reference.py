"""The comparison that decides the logit part of ``correct``.

The served path (router-less, straight to the engine's chat endpoint,
``max_tokens=1`` with the API's 20 top log-probabilities) against the
configuration's plain reference (``chipbench/references/<name>.py``,
run inside the engine child on the same weights, outside the measured
window, at the configuration's widths).

Log-probabilities are compared, not sampled tokens: with random weights
the largest logit changes on rounding.
"""

import re
from typing import Dict, List, Optional

from chipbench.harness_key import PROBE_GAP_LIMIT

TOP = 20
# Largest allowed |served - reference| over the served top-20 tokens of
# one prompt. Calibrated, not derived: the served path keeps activations
# in bfloat16 (8 bits of mantissa) and the reference in float32, and 32
# layers of RANDOM weights amplify a rounding step instead of averaging
# it away. Two implementations that are both right (Pallas kernel vs
# jax.numpy attention, tp=4 vs tp=1, same int8 weights, bf16
# activations) differed by 0.09 to 0.17 on the v5e (PERF.md, PR 21), so
# 0.3 is about twice the noise. At debug-tiny on the CPU the same
# comparison measures under 0.02 and a causal mask off by one position
# measures over 0.04 (tests/chipbench). What it cannot see is in
# PERF.md section 7.
# PR 46: a configuration whose own readings lie elsewhere, or do not
# stand three times apart on this number, states its limits in its file
# (``harness.probe``, chipbench/harness_key.py: this widest gap a
# prompt, and the mean gap over every served log-probability of the
# run); this is the default.
TOLERANCE = PROBE_GAP_LIMIT
# of the served top-20, how many the reference's own top-20 must name:
# near-ties at the tail of the list swap freely, a wrong distribution
# shares few
MIN_SHARED = 10

_UNK = re.compile(r"<unk:(\d+)>")
_SPECIAL = {"<bos>": 256, "<eos>": 257, "<pad>": 258}


def token_id(entry: Dict) -> int:
    """Token id of one ``top_logprobs`` entry of the chat API under the
    byte tokenizer: a byte is its own id; ids past the bytes are
    rendered ``<unk:N>`` (engine/tokenizer.py ByteTokenizer)."""
    raw = bytes(entry["bytes"])
    if len(raw) == 1:
        return raw[0]
    name = raw.decode()
    if name in _SPECIAL:
        return _SPECIAL[name]
    m = _UNK.fullmatch(name)
    if not m:
        raise ValueError(f"cannot map token {name!r} to an id")
    return int(m.group(1))


def compare(served: List[Dict], reference: List[Dict],
            tolerance: Optional[float] = TOLERANCE,
            mean_limit: Optional[float] = None) -> Dict:
    """``served``: per prompt {"prompt_tokens", "ids", "logprobs"} (the
    API's top-20); ``reference``: per prompt {"prompt_tokens",
    "logprobs" (at the served ids), "top_ids"}. ok only if every prompt
    is inside the tolerance (the widest gap of its twenty; None: not
    held to one) and shares enough of its top list, and the mean gap
    over every served log-probability of every prompt is inside
    ``mean_limit`` (None: not held to one)."""
    rows, ok = [], len(served) == len(reference) and bool(served)
    every = []
    for s, r in zip(served, reference):
        gaps = [abs(a - b) for a, b in
                zip(s["logprobs"], r["logprobs"], strict=True)]
        every += gaps
        shared = len(set(s["ids"]) & set(r["top_ids"]))
        good = (s["prompt_tokens"] == r["prompt_tokens"]
                and (tolerance is None or max(gaps) <= tolerance)
                and shared >= MIN_SHARED)
        ok = ok and good
        rows.append({"prompt_tokens": s["prompt_tokens"],
                     "max_abs_logprob_diff": max(gaps),
                     "mean_abs_logprob_diff": sum(gaps) / len(gaps),
                     "shared_top": shared, "ok": good})
    mean = sum(every) / len(every) if every else None
    if mean_limit is not None:
        ok = ok and mean is not None and mean <= mean_limit
    return {"ok": ok, "tolerance": tolerance, "mean_limit": mean_limit,
            "mean_abs_logprob_diff": mean, "rows": rows}

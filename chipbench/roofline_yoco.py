"""The least time the steps of a decoder-hybrid-decoder (Phi-4-mini-flash,
``phi4flash``: Mamba layers beside window attention, ONE full K/V layer
that the cross layers read, gated memory units) could take on the chip,
from the configuration's published keys and its ``assumed`` Mamba
sizes: the yardstick of ``yoco_decode_step_roofline``,
``yoco_prefill_chunk_roofline``, ``mamba_decode_kernel_roofline``,
``mamba_prefill_kernel_roofline`` and ``shared_kv_step_share``.

The same work whatever implements it. A decode step reads every weight
once, the full layer's live K and V once a READER (the layer itself and
every cross layer: nothing lets eight layers share one read), min(
context, window) keys in each window layer, and reads and writes a live
row's Mamba page once a Mamba layer. A prefill chunk runs the layers
before the first gated memory unit alone (the two depths are the
MODEL's: the cross layers need only the last position, the paper's
point), plus one position of the rest in the chunks that end a prompt.
A Mamba layer's rule is counted as the RECURRENCE: a token and (state,
channel) entry one exponential and seven operations of the vector unit
(``dt A``, the decay's product, ``dt x``, its product with ``B``, the
sum, the product with ``C``, the sum over the state); what a chunked or
parallel form computes besides is the implementation's choice.

Peaks: ``chipbench/roofline.py``'s for bytes and the matrix unit. The
recurrence never touches the matrix unit, so its operations are held to
a VECTOR-unit rate, ``VECTOR_PEAKS``: NOT a published number. The v5e's
documented peak (197 TFLOP/s bf16 over 4 matrix units of 128 x 128, two
operations a cell and cycle) gives a clock of 1.5 GHz; the vector unit
is 8 x 128 lanes (the Pallas guide's table); taken are FOUR vector
operations a lane and cycle (the vector slots of this family's VLIW
bundle as Norrie et al., "The Design Process for Google's Training
Chips: TPUv2 and TPUv3", IEEE Micro 2021, describe it; two of them are
load / store slots there, so the arithmetic rate is likely lower) and
ONE transcendental a lane and cycle: 6.16e12 operations and 1.54e12
exponentials a second. Both are on the high side on purpose: a peak
taken too high makes a share read too LOW, never over 100 %. PERF.md
section 7 asks for a measured rate.
"""

from typing import Dict

from chipbench import roofline

CACHE_BYTES = 2.0       # bfloat16 K and V, convolution inputs
STATE_BYTES = 4.0       # float32 Mamba state
KERNEL_BYTES = 4.0      # float32 x, dt, B, C in and y out of the scan
SMALL_BYTES = 2.0       # the leaves that stay bfloat16 under int8
ENTRY_OPS = 7.0         # vector operations an entry of h and token
ENTRY_EXPS = 1.0

# (vector operations, transcendentals) a second; module text
VECTOR_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"vector_ops": 4 * 1024 * 1.503e9,
                    "exps": 1024 * 1.503e9},
}


def is_yoco(hf: Dict) -> bool:
    return hf.get("model_type") == "phi4flash"


def sizes(hf: Dict) -> Dict[str, int]:
    """The model's sizes and how many layers of each kind it has."""
    a = hf.get("assumed") or {}
    h, L = hf["hidden_size"], hf["num_hidden_layers"]
    nh = hf["num_attention_heads"]
    return dict(
        h=h, i=hf["intermediate_size"], L=L, V=hf["vocab_size"], nh=nh,
        nkv=hf["num_key_value_heads"], hd=h // nh,
        W=hf["sliding_window"], di=a.get("mamba_expand", 2) * h,
        ds=a.get("mamba_d_state", 16), dc=a.get("mamba_d_conv", 4),
        r=a.get("mamba_dt_rank", -(-h // 16)),
        mamba=L // 4 + 1, window=L // 4, full=1, gmu=L // 4 - 1,
        cross=L // 4 - 1)


def kv_token_bytes(hf: Dict) -> float:
    """K and V of one token in ONE pool layer."""
    s = sizes(hf)
    return 2 * s["nkv"] * s["hd"] * CACHE_BYTES


def layer_weights(hf: Dict) -> Dict[str, tuple]:
    """kind -> (quantised, small) parameters of ONE layer's mixer;
    "block": what every layer has (fc1, fc2 | two LayerNorms)."""
    s = sizes(hf)
    h, di, hd, nh, nkv = s["h"], s["di"], s["hd"], s["nh"], s["nkv"]
    lam = 6 * hd                        # four lambda vectors, the norm
    return {
        "block": (3 * h * s["i"], 4 * h),
        "mamba": (h * 2 * di + di * h,
                  di * (s["r"] + 2 * s["ds"]) + s["r"] * di + di
                  + (s["dc"] + 1) * di + s["ds"] * di + di),
        "own": (h * (nh + 2 * nkv) * hd + nh * hd * h,
                (nh + 2 * nkv) * hd + h + lam),
        "gmu": (2 * h * di, 0),
        "cross": (2 * h * nh * hd, nh * hd + h + lam),
    }


def weights(hf: Dict, depth: str = "all",
            weight_bytes_per_param: float = 1.0):
    """(bytes of the weights a forward reads once, parameters a token
    passes through). ``depth`` "all": every layer, the final norm and
    the head; "self": the layers before the first gated memory unit
    alone; "cross": the rest, norm and head."""
    s, w = sizes(hf), layer_weights(hf)
    first = {"mamba": s["mamba"], "own": s["window"] + s["full"],
             "block": s["mamba"] + s["window"] + s["full"]}
    rest = {"gmu": s["gmu"], "cross": s["cross"],
            "block": s["gmu"] + s["cross"]}
    q = sm = 0
    for kinds in {"all": (first, rest), "self": (first,),
                  "cross": (rest,)}[depth]:
        q += sum(n * w[k][0] for k, n in kinds.items())
        sm += sum(n * w[k][1] for k, n in kinds.items())
    if depth != "self":
        q, sm = q + s["h"] * s["V"], sm + 2 * s["h"]
    return weight_bytes_per_param * q + SMALL_BYTES * sm, q + sm


def mamba_call_needs(hf: Dict, rows: float, tokens: float
                     ) -> Dict[str, float]:
    """ONE layer's selective scan over ``tokens`` positions in all of
    ``rows`` rows: each row's state in and out once, x, dt, B, C in and
    y out, the recurrence's vector operations and exponentials (no
    operation of the matrix unit)."""
    s = sizes(hf)
    entries = tokens * s["ds"] * s["di"]
    return {"bytes": rows * 2.0 * s["ds"] * s["di"] * STATE_BYTES
            + tokens * (3 * s["di"] + 2 * s["ds"]) * KERNEL_BYTES,
            "ops": 0.0, "vector_ops": ENTRY_OPS * entries,
            "exps": ENTRY_EXPS * entries}


def attend_ops(hf: Dict, keys: float) -> float:
    """Operations of ONE differential-attention layer over ``keys``
    (query, key) pairs: a query head's score over hd and its weighted
    sum over a value of 2 hd."""
    s = sizes(hf)
    return keys * s["nh"] * 2.0 * (s["hd"] + 2 * s["hd"])


def decode_step_needs(hf: Dict, rows: float, context_tokens: float,
                      window_tokens: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of ``rows`` live rows whose contexts sum to
    ``context_tokens`` and, each cut to the window, to
    ``window_tokens`` (``context_sums``)."""
    s = sizes(hf)
    read, passed = weights(hf, "all", weight_bytes_per_param)
    readers = s["full"] + s["cross"]
    scan = mamba_call_needs(hf, rows, rows)
    conv = rows * 2.0 * (s["dc"] - 1) * s["di"] * CACHE_BYTES
    keys = readers * context_tokens + s["window"] * window_tokens
    return {"bytes": read + keys * kv_token_bytes(hf)
            + s["mamba"] * (scan["bytes"] + conv),
            "ops": 2.0 * rows * passed + attend_ops(hf, keys),
            "vector_ops": s["mamba"] * scan["vector_ops"],
            "exps": s["mamba"] * scan["exps"],
            "shared_bytes": s["cross"] * context_tokens
            * kv_token_bytes(hf)}


def context_sums(hf: Dict, contexts) -> tuple:
    """(rows, the contexts' sum, the sum of each cut to the window)."""
    W = sizes(hf)["W"]
    return (len(contexts), float(sum(contexts)),
            float(sum(min(c, W) for c in contexts)))


def prefill_chunk_needs(hf: Dict, tokens: int, in_context: float,
                        finishing: float = 0.0,
                        weight_bytes_per_param: float = 1.0
                        ) -> Dict[str, float]:
    """ONE prefill chunk of ``tokens`` queries of one row that have on
    average ``in_context`` keys at or before them, over the layers
    before the first gated memory unit; ``finishing`` (0..1: the share
    of chunks that end a prompt) of one position of the rest, its
    weights and the shared layer's keys. Bytes: those weights once, K
    and V of the context in the full layer and of the window and the
    chunk in each window layer, the row's page in and out in every
    Mamba layer. Operations: two per weight a token passes, the causal
    products, the recurrence."""
    s = sizes(hf)
    read, passed = weights(hf, "self", weight_bytes_per_param)
    tail_read, tail_passed = weights(hf, "cross", weight_bytes_per_param)
    context = in_context + tokens / 2.0         # the chunk's last query's
    scan = mamba_call_needs(hf, 1, tokens)
    window_keys = min(context, s["W"] + tokens)
    seen = min(in_context, s["W"])              # keys a window query sees
    return {"bytes": read + s["mamba"] * scan["bytes"]
            + (s["full"] * context + s["window"] * window_keys
               + finishing * s["cross"] * context) * kv_token_bytes(hf)
            + finishing * tail_read,
            "ops": tokens * 2.0 * passed
            + attend_ops(hf, tokens * (s["full"] * in_context
                                       + s["window"] * seen))
            + finishing * (2.0 * tail_passed
                           + attend_ops(hf, s["cross"] * context)),
            "vector_ops": s["mamba"] * scan["vector_ops"],
            "exps": s["mamba"] * scan["exps"]}


def least_seconds(needs: Dict[str, float], device_kind: str) -> Dict:
    """``roofline.least_seconds`` with the vector unit's two rates
    beside bytes and the matrix unit: the largest of the four, and
    which it is."""
    out = roofline.least_seconds(needs, device_kind)
    if device_kind not in VECTOR_PEAKS:
        raise KeyError(f"no vector peaks taken for device kind "
                       f"{device_kind!r}; add them to "
                       f"chipbench/roofline_yoco.py with their source")
    peaks = VECTOR_PEAKS[device_kind]
    for key, bound in (("vector_ops", "vector operations"),
                       ("exps", "exponentials")):
        t = needs.get(key, 0.0) / peaks[key]
        out["by_" + key + "_s"] = t
        if t > out["seconds"]:
            out["seconds"], out["bound"] = t, bound
    return out

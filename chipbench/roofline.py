"""The least time a decode step could take on the chip, from the
configuration's shapes: the yardstick of ``decode_step_roofline``.

Kept with the benchmark so that no PR that claims a gain can move it.
"""

from typing import Dict

# Peaks per chip by jax ``device_kind``. Source: Google Cloud
# documentation, "TPU v5e" (system architecture): 197 TFLOP/s bf16,
# 393 TOP/s int8, 819 GB/s HBM. A device that is not here is an error,
# never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9},
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to chipbench/roofline.py with its source")
    return PEAKS[device_kind]


def decode_step_needs(hf: Dict, rows: int, context_tokens: int,
                      weight_bytes_per_param: float = 1.0,
                      kv_bytes_per_value: float = 2.0) -> Dict[str, float]:
    """Bytes and operations ONE decode step needs for ``rows`` live
    sequences whose contexts sum to ``context_tokens``.

    Bytes: every weight the step must read once (attention projections,
    the MLP or, for a mixture of experts, the router, the shared expert
    and the experts that ``rows x top_k`` assignments are expected to
    touch: E x (1 - (1 - 1/E)^(rows x k)), the expectation under even
    routing, NOT all experts), the output head, and the cached keys and
    values of every live context. Operations: two per weight per row
    for every weight a token passes through (its top-k experts only),
    plus, per query head, two per cached key element and two per
    cached value element (scores and the weighted sum).
    Embedding rows, norms and activations are left out: under a
    thousandth of the rest."""
    h, L = hf["hidden_size"], hf["num_hidden_layers"]
    nh, nkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = hf.get("head_dim") or h // nh
    attn = h * nh * hd * 2 + h * nkv * hd * 2          # q, o, k, v
    E = hf.get("num_experts") or 0
    if E:
        k = hf["num_experts_per_tok"]
        expert = 3 * h * hf["moe_intermediate_size"]
        shared = 3 * h * hf.get("shared_expert_intermediate_size", 0)
        touched = E * (1.0 - (1.0 - 1.0 / E) ** (rows * k))
        mlp_read = h * E + shared + touched * expert
        mlp_pass = h * E + shared + k * expert
    else:
        mlp_read = mlp_pass = 3 * h * hf["intermediate_size"]
    head = h * hf["vocab_size"]
    kv_values = context_tokens * L * 2 * nkv * hd
    weights_read = L * (attn + mlp_read) + head
    weights_pass = L * (attn + mlp_pass) + head
    return {"bytes": weights_read * weight_bytes_per_param
            + kv_values * kv_bytes_per_value,
            "ops": 2.0 * rows * weights_pass
            + 2.0 * kv_values * (nh / nkv)}


def least_seconds(needs: Dict[str, float], device_kind: str) -> Dict:
    """The larger of bytes over peak bandwidth and operations over peak
    bf16 rate (int8 weights are multiplied as bf16: weight-only
    quantisation), and which of the two it is."""
    peaks = peaks_for(device_kind)
    by_bytes = needs["bytes"] / peaks["hbm_bytes_per_s"]
    by_ops = needs["ops"] / peaks["bf16_flops"]
    return {"seconds": max(by_bytes, by_ops),
            "bound": "bytes" if by_bytes >= by_ops else "operations",
            "by_bytes_s": by_bytes, "by_ops_s": by_ops}

"""The least time the steps of a hybrid of Gated DeltaNet and gated
attention layers (Qwen3-Next, ``qwen3_next``) could take on the chip,
from the configuration's published keys: the yardstick of
``hybrid_decode_step_roofline``, ``hybrid_prefill_chunk_roofline``,
``gdn_decode_kernel_roofline`` and ``gdn_prefill_kernel_roofline``.

The same work whatever implements it. A Gated DeltaNet layer's rule is
counted as the RECURRENCE: a token and value head decays, reads and
writes one ``Dk x Dv`` matrix, three products of ``2 Dk Dv`` operations
(``k^T S``, ``k d^T``, ``q^T S``), and a call reads and writes a live
row's state ONCE; what a chunked form multiplies besides is the
implementation's choice and is not counted. An attention layer reads K
and V of every live context. Every weight outside the routed experts is
read once a step; of the experts held here a layer reads ``touched``.
Peaks and ``least_seconds`` are ``chipbench/roofline.py``'s.

ONE input is not the file's: how many of the held experts a layer of a
decode step read (``touched``), from the program's counter
``totals.moe``.
"""

from typing import Dict, Sequence

CACHE_BYTES = 2.0       # bfloat16 K and V, convolution inputs
STATE_BYTES = 4.0       # float32 state matrices
ACT_BYTES = 2.0         # bfloat16 activations in and out of a kernel
SMALL_BYTES = 2.0       # the leaves that stay bfloat16 under int8


def layer_counts(hf: Dict):
    """(Gated DeltaNet layers, attention layers): of every
    ``full_attention_interval`` layers the last is attention."""
    L = hf["num_hidden_layers"]
    attn = L // hf["full_attention_interval"]
    return L - attn, attn


def gdn_sizes(hf: Dict):
    """(key heads x key dim, value heads, key dim, value dim,
    convolution channels)."""
    hk, hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    dk, dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    return hk * dk, hv, dk, dv, 2 * hk * dk + hv * dv


def mixer_weights(hf: Dict):
    """((quantised, small) parameters of a Gated DeltaNet mixer, the
    same of an attention mixer): in_proj_qkvz and out_proj | in_proj_ba,
    the convolution, A_log, dt_bias and the output norm; q (query and
    gate), k, v, o | the two head norms."""
    h = hf["hidden_size"]
    _, hv, _, dv, ch = gdn_sizes(hf)
    nh, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    gdn = (h * (ch + hv * dv) + hv * dv * h,
           h * 2 * hv + ch * hf["linear_conv_kernel_dim"] + 2 * hv + dv)
    attn = (h * 2 * nh * hd + 2 * h * nkv * hd + nh * hd * h, 2 * hd)
    return gdn, attn


def conv_state_bytes(hf: Dict) -> float:
    """The inputs one Gated DeltaNet layer's convolution keeps a
    sequence (a hundredth of the matrices)."""
    return ((hf["linear_conv_kernel_dim"] - 1) * gdn_sizes(hf)[4]
            * CACHE_BYTES)


def state_bytes(hf: Dict) -> float:
    """One sequence's state in ONE Gated DeltaNet layer, as a step
    reads or writes it: the matrices and the convolution's inputs."""
    _, hv, dk, dv, _ = gdn_sizes(hf)
    return hv * dk * dv * STATE_BYTES + conv_state_bytes(hf)


def gdn_call_needs(hf: Dict, rows: int, tokens: int) -> Dict[str, float]:
    """ONE layer's delta rule over ``tokens`` positions in all of
    ``rows`` live rows: the recurrence's operations, q, k, v, g and
    beta in, o out, and each row's matrices in and out once."""
    qk, hv, dk, dv, _ = gdn_sizes(hf)
    per_token = ((2 * qk + hv * dv) * ACT_BYTES     # q, k, v
                 + 2 * hv * 4.0                     # g, beta
                 + hv * dv * ACT_BYTES)             # o
    return {"bytes": tokens * per_token
            + rows * 2.0 * hv * dk * dv * STATE_BYTES,
            "ops": tokens * hv * 6.0 * dk * dv}


def attention_call_needs(hf: Dict, contexts: Sequence[float]
                         ) -> Dict[str, float]:
    """ONE layer's attention of one query a row: K and V of every
    context once, two operations a query head and cached value."""
    nh, nkv, hd = (hf["num_attention_heads"], hf["num_key_value_heads"],
                   hf["head_dim"])
    keys = float(sum(contexts))
    return {"bytes": keys * 2 * nkv * hd * CACHE_BYTES,
            "ops": 2.0 * keys * 2 * nh * hd}


def weights(hf: Dict, experts_read: float, experts_pass: float,
            weight_bytes_per_param: float = 1.0):
    """(bytes of the weights a forward reads once, parameters a token
    passes through): every mixer, every layer's router at its published
    width, gated shared expert and two norms, ``experts_read`` of the
    held experts a layer (``experts_pass``: a token's share), and the
    head."""
    h, L = hf["hidden_size"], hf["num_hidden_layers"]
    n_gdn, n_attn = layer_counts(hf)
    (gdn_q, gdn_s), (attn_q, attn_s) = mixer_weights(hf)
    expert = 3 * h * hf["moe_intermediate_size"]
    shared = 3 * h * hf.get("shared_expert_intermediate_size", 0)
    router = h * (hf.get("deployment") or {}).get(
        "router_experts", hf["num_experts"])
    quantised = (n_gdn * gdn_q + n_attn * attn_q
                 + L * shared + h * hf["vocab_size"])
    small = (n_gdn * gdn_s + n_attn * attn_s + L * (router + h + 2 * h)
             + h)
    return (weight_bytes_per_param * (quantised + L * experts_read * expert)
            + SMALL_BYTES * small,
            quantised + small + L * experts_pass * expert)


def held_share(hf: Dict) -> float:
    """The share of a token's top-k assignments that land on experts
    held here under even routing."""
    return hf["num_experts"] / (hf.get("deployment") or {}).get(
        "router_experts", hf["num_experts"])


def decode_step_needs(hf: Dict, contexts: Sequence[float], touched: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of len(contexts) live rows: every weight read
    once (``touched`` of the held experts a layer), K and V of the live
    contexts in every attention layer, a live row's state read and
    written once in every Gated DeltaNet layer."""
    n_gdn, n_attn = layer_counts(hf)
    rows = len(contexts)
    read, passed = weights(hf, touched,
                           hf["num_experts_per_tok"] * held_share(hf),
                           weight_bytes_per_param)
    attend = attention_call_needs(hf, contexts)
    rule = gdn_call_needs(hf, rows, rows)      # the matrices are in it
    conv = rows * 2.0 * conv_state_bytes(hf)
    return {"bytes": read + n_attn * attend["bytes"]
            + n_gdn * (rule["bytes"] + conv),
            "ops": 2.0 * rows * passed + n_attn * attend["ops"]
            + n_gdn * rule["ops"]}


def prefill_chunk_needs(hf: Dict, tokens: int, in_context: float,
                        weight_bytes_per_param: float = 1.0
                        ) -> Dict[str, float]:
    """ONE prefill chunk of ``tokens`` queries of one row that have on
    average ``in_context`` keys at or before them. Bytes: every weight
    once (every held expert), K and V of the context in every
    attention layer, the row's state in and out in every Gated
    DeltaNet layer. Operations: two per weight a token passes, the
    causal products over what a query has in context, the recurrence.
    The logits are reckoned for every position, as the program computes
    them."""
    n_gdn, n_attn = layer_counts(hf)
    nh, hd = hf["num_attention_heads"], hf["head_dim"]
    read, passed = weights(hf, hf["num_experts"],
                           hf["num_experts_per_tok"] * held_share(hf),
                           weight_bytes_per_param)
    context = in_context + tokens / 2.0         # the chunk's last query's
    rule = gdn_call_needs(hf, 1, tokens)
    return {"bytes": read
            + n_attn * attention_call_needs(hf, [context])["bytes"]
            + n_gdn * rule["bytes"],
            "ops": tokens * (2.0 * passed
                             + n_attn * 4.0 * in_context * nh * hd)
            + n_gdn * rule["ops"]}

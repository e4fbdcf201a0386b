"""The least time the steps of a looped decoder (``ouro``: the whole
layer stack run ``total_ut_steps`` times on every token with the same
weights, each pass over K and V of its own) could take on the chip, from
the configuration's published keys: the yardstick of
``looped_decode_step_roofline``, ``looped_prefill_chunk_roofline``,
``looped_attention_kernel_roofline`` and ``loop_kv_step_share``.

The same work whatever implements it. A decode step reads every layer's
weights once a PASS (the stack is larger than any on-chip memory, so a
pass cannot reuse what the pass before read), the head once, and K and
V of every live context in every POOL layer (passes x layers: a pass
attends over its own), and writes the step's own K and V there. A
prefill chunk is held to the larger of its operations at the matrix
unit's peak and its bytes: two operations a weight a token and pass,
the causal attention products of every pool layer, the head for ONE
position a row (the next token's; logits of the other positions are
nobody's need).

Peaks: ``chipbench/roofline.py``'s.
"""

from typing import Dict

from chipbench import roofline

CACHE_BYTES = 2.0       # bfloat16 K and V
SMALL_BYTES = 2.0       # the norms, which stay bfloat16 under int8
GATE_BYTES = 4.0        # the exit gate, float32


def is_looped(hf: Dict) -> bool:
    return hf.get("model_type") == "ouro"


def sizes(hf: Dict) -> Dict[str, int]:
    h, nh = hf["hidden_size"], hf["num_attention_heads"]
    return dict(
        h=h, L=hf["num_hidden_layers"], P=hf.get("total_ut_steps", 1),
        nh=nh, nkv=hf.get("num_key_value_heads", nh),
        hd=hf.get("head_dim") or h // nh, i=hf["intermediate_size"],
        V=hf["vocab_size"])


def layer_weights(hf: Dict) -> tuple:
    """(quantised, small) parameters of ONE layer: q, k, v, o, gate,
    up, down; its four norms."""
    s = sizes(hf)
    return (2 * s["h"] * s["nh"] * s["hd"] + 2 * s["h"] * s["nkv"] * s["hd"]
            + 3 * s["h"] * s["i"], 4 * s["h"])


def kv_token_bytes(hf: Dict) -> float:
    """K and V of one token in EVERY pool layer (passes x layers)."""
    s = sizes(hf)
    return s["P"] * s["L"] * 2 * s["nkv"] * s["hd"] * CACHE_BYTES


def pass_bytes(hf: Dict, weight_bytes_per_param: float = 1.0) -> float:
    """Every weight ONE pass reads: the layers, the final norm, the
    exit gate."""
    s = sizes(hf)
    q, small = layer_weights(hf)
    return (s["L"] * (weight_bytes_per_param * q + SMALL_BYTES * small)
            + SMALL_BYTES * s["h"] + GATE_BYTES * (s["h"] + 1))


def head_bytes(hf: Dict, weight_bytes_per_param: float = 1.0) -> float:
    s = sizes(hf)
    return weight_bytes_per_param * s["h"] * s["V"]


def attend_ops(hf: Dict, keys: float) -> float:
    """Operations of ONE pool layer over ``keys`` (query, key) pairs: a
    query head's score and its weighted sum over hd each."""
    s = sizes(hf)
    return keys * s["nh"] * 4.0 * s["hd"]


def decode_step_parts(hf: Dict, rows: float, context_tokens: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """The bytes of ONE decode step by what they are: ``weights`` the
    layers, the final norm and the gate once a pass; ``head``; ``kv``
    the live contexts' K and V in every pool layer and the step's own
    written there."""
    s = sizes(hf)
    return {"weights": s["P"] * pass_bytes(hf, weight_bytes_per_param),
            "head": head_bytes(hf, weight_bytes_per_param),
            "kv": (context_tokens + rows) * kv_token_bytes(hf)}


def decode_step_needs(hf: Dict, rows: float, context_tokens: float,
                      weight_bytes_per_param: float = 1.0
                      ) -> Dict[str, float]:
    """ONE decode step of ``rows`` live rows whose contexts sum to
    ``context_tokens``."""
    s = sizes(hf)
    q, small = layer_weights(hf)
    return {"bytes": sum(decode_step_parts(
                hf, rows, context_tokens, weight_bytes_per_param).values()),
            "ops": 2.0 * rows * (s["P"] * s["L"] * q + s["h"] * s["V"])
            + attend_ops(hf, s["P"] * s["L"] * context_tokens)}


def attention_call_needs(hf: Dict, rows: float, context_tokens: float
                         ) -> Dict[str, float]:
    """ONE call of the decode attention (one pool layer): K and V of
    the live contexts read once, the queries in and the outputs out."""
    s = sizes(hf)
    return {"bytes": context_tokens * 2 * s["nkv"] * s["hd"] * CACHE_BYTES
            + rows * 2 * s["nh"] * s["hd"] * CACHE_BYTES,
            "ops": attend_ops(hf, context_tokens)}


def prefill_chunk_needs(hf: Dict, rows: float, tokens: float,
                        before: float = 0.0,
                        weight_bytes_per_param: float = 1.0
                        ) -> Dict[str, float]:
    """ONE prefill dispatch of ``rows`` rows of ``tokens`` real tokens
    each, a row's first token with ``before`` keys ahead of it: every
    weight once a pass, K and V of the context read and the chunk's
    written in every pool layer; two operations a weight a token and
    pass, the causal products, the head for one position a row."""
    s = sizes(hf)
    q, small = layer_weights(hf)
    pairs = rows * tokens * (before + (tokens + 1) / 2.0)
    return {"bytes": s["P"] * pass_bytes(hf, weight_bytes_per_param)
            + head_bytes(hf, weight_bytes_per_param)
            + rows * (before + 2 * tokens) * kv_token_bytes(hf),
            "ops": 2.0 * rows * tokens * s["P"] * s["L"] * q
            + 2.0 * rows * s["h"] * s["V"]
            + attend_ops(hf, s["P"] * s["L"] * pairs)}


least_seconds = roofline.least_seconds
